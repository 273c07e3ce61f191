"""Static-analysis (graph verifier & hazard linter) tests.

Seeded-hazard fixtures — use-after-donation, nondeterministic bucket
order, cache-churn attrs, and one per precision-flow rule
(QT701–QT705) — each tripping exactly one rule, plus zero-false-
positive gates over the bundled model zoo (f32 / simulated-bf16 /
int8-quantized) and the ZeRO/scan/bucketed configurations, the GV/HS
rule set, bind-time warn/raise surfaces, telemetry mirroring,
suppression, the registration-time infer-signature validation, the
Pallas kernel-spec validator (PK9xx), the env-var doc-sync audit, and
the cost-metadata consistency contract.
"""
import json
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.analysis import (AnalysisContext, RULES, lint_json,
                                lint_executor, lint_module, lint_symbol,
                                run_passes)
from mxnet_tpu.kvstore_sched import BucketScheduler
from mxnet_tpu.ops.registry import OpDef
from mxnet_tpu.program_cache import attr_cache_stable


def _precision_rules(sym, **ctx_kwargs):
    report = run_passes(AnalysisContext(symbol=sym, **ctx_kwargs),
                        passes=["precision_flow"])
    return report


def _two_fc():
    """Two same-shape FC layers: aliasing one weight cell onto the
    other keeps every shape consistent (the donation fixture must trip
    DA201 alone, not a shape rule)."""
    d = mx.sym.var("data")
    h = mx.sym.FullyConnected(d, num_hidden=16, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="r1")
    h = mx.sym.FullyConnected(h, num_hidden=16, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


def _fused_module():
    mod = mx.mod.Module(_two_fc(), context=mx.cpu())
    mod.bind(data_shapes=[("data", (8, 16))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params()
    mod.init_optimizer(kvstore=None)
    assert mod._fused_armed
    return mod


def _mlp():
    d = mx.sym.var("data")
    h = mx.sym.FullyConnected(d, num_hidden=8, name="fc1")
    h = mx.sym.Activation(h, act_type="relu", name="r1")
    h = mx.sym.FullyConnected(h, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(h, name="softmax")


# ------------------------------------------------------ seeded fixtures
def test_fixture_use_after_donation():
    """Aliasing a second arg name onto a donated param cell trips DA201
    and nothing else."""
    mod = _fused_module()
    exe = mod._exec_group.executor
    i1 = exe.arg_names.index("fc1_weight")
    i2 = exe.arg_names.index("fc2_weight")
    exe.arg_arrays[i2] = exe.arg_arrays[i1]
    report = lint_module(mod)
    assert report.rules == {"DA201"}
    assert len(report) == 1
    d = report.errors[0]
    assert "fc1_weight" in d.message and "fc2_weight" in d.message


def test_fixture_nondeterministic_bucket_order():
    """Equal-priority keys staged from two push calls in one window
    trip CO301 (multiworker audit) and nothing else."""
    sched = BucketScheduler(lambda x: x, lambda k, c, v: None,
                            lambda: 1 << 30)
    sched.note_push_call()
    sched.stage(3, None, np.zeros(4, np.float32), priority=0)
    sched.note_push_call()
    sched.stage(5, None, np.zeros(4, np.float32), priority=0)
    report = run_passes(AnalysisContext(sched=sched,
                                        assume_multiworker=True))
    assert report.rules == {"CO301"}
    assert len(report) == 1
    # same plan is fine on a single worker (no divergence possible)
    assert not len(run_passes(AnalysisContext(sched=sched)))


def test_fixture_cache_churn_attr():
    """An array-valued op attr trips RC401 and nothing else."""
    net = _mlp()
    node = net._outputs[0][0]
    node.attrs["debug_buffer"] = np.arange(3)
    report = lint_symbol(net, shapes={"data": (2, 8)})
    assert report.rules == {"RC401"}
    assert len(report) == 1
    assert "debug_buffer" in report.warnings[0].message


# -------------------------------------------------- zero-false-positive
MODEL_SHAPES = [
    ("mlp", lambda m: m.mlp.get_symbol(10), {"data": (8, 784)}),
    ("lenet", lambda m: m.lenet.get_symbol(10), {"data": (8, 1, 28, 28)}),
    ("alexnet", lambda m: m.alexnet.get_symbol(10),
     {"data": (2, 3, 224, 224)}),
    ("vgg16", lambda m: m.vgg.get_symbol(10, 16),
     {"data": (1, 3, 224, 224)}),
    ("resnet20", lambda m: m.resnet.get_symbol(10, 20, "3,32,32"),
     {"data": (4, 3, 32, 32)}),
    ("inception_bn", lambda m: m.inception_bn.get_symbol(10),
     {"data": (1, 3, 224, 224)}),
    ("inception_v3", lambda m: m.inception_v3.get_symbol(10),
     {"data": (1, 3, 299, 299)}),
]


@pytest.mark.parametrize("name,build,shapes", MODEL_SHAPES,
                         ids=[m[0] for m in MODEL_SHAPES])
def test_bundled_models_lint_clean(name, build, shapes):
    from mxnet_tpu import models
    report = lint_symbol(build(models), shapes=shapes)
    assert not len(report), f"{name}: {report.format()}"


@pytest.mark.parametrize("name,build,shapes", MODEL_SHAPES,
                         ids=[m[0] for m in MODEL_SHAPES])
def test_bundled_models_bf16_precision_clean(name, build, shapes):
    """Simulated-bf16 compute over the zoo: the QT7xx pass must stay
    quiet (the mixed-precision entry cast is uniform — no mixing)."""
    from mxnet_tpu import models
    report = lint_symbol(build(models), shapes=shapes,
                         compute_dtype="bfloat16")
    assert not len(report), f"{name}@bf16: {report.format()}"


@pytest.mark.parametrize("name,build,shapes", MODEL_SHAPES,
                         ids=[m[0] for m in MODEL_SHAPES])
def test_bundled_models_int8_quantized_lint_clean(name, build, shapes):
    """The int8 quant-rewritten zoo lints clean: declared int8 cells,
    Quantized* weight contracts, no QT/GV findings."""
    from mxnet_tpu import models
    qsym, _qargs = _quantized_model(lambda: build(models), shapes)
    report = lint_symbol(qsym, shapes=shapes)
    assert not len(report), f"{name}@int8: {report.format()}"


def test_gv105_quantized_cells_bind_without_warning():
    """GV105 regression gate: the quant rewrite's declared __dtype__
    int8 cells must bind int8 and pass dtype validation with zero
    warn-mode findings — for the MLP and a convnet."""
    from mxnet_tpu import models
    cases = [(models.mlp.get_symbol(10), {"data": (8, 784)}),
             (models.lenet.get_symbol(10), {"data": (8, 1, 28, 28)})]
    for sym, shapes in cases:
        qsym, qargs = _quantized_model(lambda s=sym: s, shapes)
        exe = qsym.simple_bind(ctx=mx.cpu(), grad_req="null",
                               validate=None, **shapes)
        # the executor honored the declarations (int8 cells bound)
        bound = dict(zip(exe.arg_names, exe.arg_arrays))
        qcells = [nm for nm in bound if nm.endswith("_q")]
        assert qcells
        for nm in qcells:
            assert str(np.dtype(bound[nm].dtype)) == "int8", nm
        report = lint_executor(exe)
        assert not len(report), report.format()


def test_fused_module_lint_clean():
    """The plain fused (replicated) arrangement has zero findings."""
    report = lint_module(_fused_module())
    assert not len(report), report.format()


def test_zero_scan_config_lint_clean():
    """The ZeRO-1 + K-step-scan arrangement on the 8-device mesh —
    the config test_zero/test_scan_fit exercise — has zero findings."""
    X = np.random.rand(32, 8).astype(np.float32)
    Y = np.zeros(32, np.float32)
    it = mx.io.NDArrayIter(X, Y, batch_size=8, label_name="softmax_label")
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(8)])
    mod.fit(it, num_epoch=1, zero_stage=1, steps_per_dispatch=2,
            kvstore=None)
    assert mod._exec_group._zero_plan is not None
    report = lint_module(mod)
    assert not len(report), report.format()


def test_kvstore_bucket_plan_lint_clean():
    """Module.update's push contract — ONE call, distinct priorities —
    audits clean even under the multiworker assumption."""
    kv = mx.kv.create("dist_sync")
    try:
        kv.init(0, mx.nd.zeros((4,)))
        kv.init(1, mx.nd.zeros((4,)))
        kv.push([1, 0], [mx.nd.ones((4,)), mx.nd.ones((4,))],
                priority=[1, 0])
        kv.pull([0, 1], [mx.nd.zeros((4,)), mx.nd.zeros((4,))])
        report = run_passes(AnalysisContext(kvstore=kv, sched=kv._sched,
                                            assume_multiworker=True))
        assert not len(report), report.format()
    finally:
        kv.close()


# ----------------------------------------------------- precision flow
def test_fixture_qt701_silent_f32_upcast():
    """A stock-f32 creation op mixed into a bf16 compute graph widens
    the chain silently -> QT701 and nothing else."""
    net = mx.sym.var("a") + mx.sym.zeros((4, 8))
    report = _precision_rules(net, compute_dtype="bfloat16")
    assert report.rules == {"QT701"}
    assert len(report) == 1
    # same graph at full f32: no reduced inputs, no finding
    assert not len(_precision_rules(net))


def test_fixture_qt702_unrewritten_quant_weight():
    """A Quantized op fed a float weight (no int8+scale rewrite) is an
    error -> QT702 alone."""
    q = mx.sym.QuantizedFullyConnected(
        mx.sym.var("data"), mx.sym.var("w"),
        mx.sym.var("s", dtype="float32"), num_hidden=8, no_bias=True,
        name="qfc")
    report = _precision_rules(q)
    assert report.rules == {"QT702"}
    assert report.errors and "w" in report.errors[0].message


def test_fixture_qt703_shared_int8_weight():
    """The int8 weight also feeding a float consumer -> QT703 alone."""
    wq = mx.sym.var("w_q", dtype="int8")
    q = mx.sym.QuantizedFullyConnected(
        mx.sym.var("data"), wq, mx.sym.var("s", dtype="float32"),
        num_hidden=8, no_bias=True, name="qfc")
    report = _precision_rules(mx.Group([q, mx.sym.sum(wq)]))
    assert report.rules == {"QT703"}
    assert "w_q" in report.errors[0].message


def test_fixture_qt704_dequant_requant_roundtrip():
    """int8 -> float -> (movement) -> int8 is a round trip -> QT704."""
    v = mx.sym.var("q", dtype="int8")
    f = mx.sym.Flatten(mx.sym.Cast(v, dtype="float32"))
    report = _precision_rules(mx.sym.Cast(f, dtype="int8"))
    assert report.rules == {"QT704"}
    # a single explicit dequant (no requant) is NOT a round trip
    assert not len(_precision_rules(mx.sym.Cast(v, dtype="float32")))


def test_fixture_qt705_narrow_loss_accumulation():
    """A loss head whose declared input dtype is bf16 -> QT705 alone;
    compute_dtype-driven reduction (f32 master params) is exempt."""
    d = mx.sym.var("data", dtype="bfloat16")
    w = mx.sym.var("w", dtype="bfloat16")
    b = mx.sym.var("b", dtype="bfloat16")
    fc = mx.sym.FullyConnected(d, weight=w, bias=b, num_hidden=4,
                               name="fc")
    report = _precision_rules(mx.sym.SoftmaxOutput(fc, name="softmax"))
    assert report.rules == {"QT705"}
    # the exemption: an all-f32 graph under bf16 compute_dtype keeps
    # its f32 master accumulation -> clean
    clean = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=4,
                              name="fc2"), name="softmax2")
    assert not len(_precision_rules(clean, compute_dtype="bfloat16"))


def _quantized_model(build, shapes):
    """Int8 quant-rewrite of a bundled model with zero weights (the
    rewrite and lint surfaces are shape/dtype-driven)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.quant import quantize_symbol
    sym = build()
    arg_shapes, _o, _a = sym.infer_shape(**shapes)
    args = {nm: mx.nd.NDArray(jnp.zeros(s, np.float32))
            for nm, s in zip(sym.list_arguments(), arg_shapes)
            if nm not in shapes}
    return quantize_symbol(sym, args)


# -------------------------------------------------------- graph verifier
def test_gv_duplicate_variable():
    a = mx.sym.var("x")
    b = mx.sym.var("x")
    report = lint_symbol(a + b)
    assert report.rules == {"GV103"}


def test_gv_duplicate_node_name():
    d = mx.sym.var("data")
    h = mx.sym.FullyConnected(d, weight=mx.sym.var("w1"),
                              bias=mx.sym.var("b1"), num_hidden=4,
                              name="fc")
    h = mx.sym.FullyConnected(h, weight=mx.sym.var("w2"),
                              bias=mx.sym.var("b2"), num_hidden=4,
                              name="fc")
    report = lint_symbol(h, shapes={"data": (2, 4)})
    assert report.rules == {"GV104"}


def test_gv_inference_conflict_is_error():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    report = lint_symbol(a + b, shapes={"a": (2, 3), "b": (4, 5)})
    assert report.rules == {"GV101"}
    msg = report.errors[0].message
    assert "_plus" in msg and "(2, 3)" in msg and "(4, 5)" in msg


def test_gv_stall_without_infer_shape():
    """An op with neither infer_shape nor shape_passthrough stalls on a
    partial input shape -> GV107 names the op. (Flatten used to be the
    fixture; it now registers a pure-python infer_shape for the
    trace-free memory planner, so a scratch op seeds the stall.)"""
    from mxnet_tpu.ops.registry import OP_REGISTRY, register
    from mxnet_tpu.symbol import _create
    if "lint_stall_fixture" not in OP_REGISTRY:
        register("lint_stall_fixture",
                 simple=lambda attrs, x: x.reshape(x.shape[0], -1))
    d = mx.sym.var("data", shape=(0, 5))     # batch unknown
    net = _create("lint_stall_fixture", [d])
    report = lint_symbol(net)
    assert "GV107" in report.rules
    assert any(f.op == "lint_stall_fixture" for f in report)


def test_flatten_infers_without_abstract_eval():
    """Flatten's registered infer_shape propagates partial batch dims
    in pure python (no eval_shape fallback)."""
    d = mx.sym.var("data", shape=(0, 5))
    net = mx.sym.Flatten(d)
    assert "GV107" not in lint_symbol(net).rules
    _, outs, _ = net.infer_shape_partial()
    assert outs == [(0, 5)]


def test_gv_shape_passthrough_flag_infers_and_silences():
    """softmax declares shape_passthrough: partial shapes flow through
    it (forward and backward) and GV107 stays quiet."""
    d = mx.sym.var("data", shape=(0, 7))
    net = mx.sym.softmax(d)
    report = lint_symbol(net)
    assert "GV107" not in report.rules
    # and the flag actually propagates shapes both ways
    _, outs, _ = net.infer_shape_partial(data=(4, 7))
    assert outs == [(4, 7)]


def test_gv_dtype_conflict():
    """An explicitly bound array conflicting with the declared dtype
    trips GV105 (simple_bind now honors declarations itself — the
    conflict needs a user-provided array)."""
    d = mx.sym.var("data", dtype="float16")
    net = mx.sym.FullyConnected(d, num_hidden=4, name="fc")
    args = {"data": mx.nd.zeros((2, 8)),           # f32, declared f16
            "fc_weight": mx.nd.zeros((4, 8)),
            "fc_bias": mx.nd.zeros((4,))}
    exe = net.bind(mx.cpu(), args=args, grad_req="null", validate=None)
    from mxnet_tpu.analysis import lint_executor
    report = lint_executor(exe)
    assert "GV105" in report.rules


def test_simple_bind_honors_declared_dtype():
    """simple_bind binds a declared __dtype__ cell (the quant tier's
    int8 weights) instead of silently upcasting to f32."""
    d = mx.sym.var("data", dtype="float16")
    net = mx.sym.FullyConnected(d, num_hidden=4, name="fc")
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8), validate=None)
    bound = dict(zip(exe.arg_names, exe.arg_arrays))
    assert str(np.dtype(bound["data"].dtype)) == "float16"
    from mxnet_tpu.analysis import lint_executor
    assert "GV105" not in lint_executor(exe).rules


def test_json_dead_node_and_dangling_input():
    doc = {"nodes": [
        {"op": "null", "name": "a", "inputs": []},
        {"op": "null", "name": "dead", "inputs": []},
        {"op": "_copy", "name": "c", "inputs": [[0, 0, 0]]}],
        "arg_nodes": [0, 1], "heads": [[2, 0, 0]]}
    report = lint_json(json.dumps(doc))
    assert "GV108" in report.rules
    assert any(f.node == "dead" for f in report)

    doc2 = {"nodes": [{"op": "_copy", "name": "c",
                       "inputs": [[5, 0, 0]]}],
            "arg_nodes": [], "heads": [[0, 0, 0]]}
    report2 = lint_json(json.dumps(doc2))
    assert "GV106" in report2.rules


def test_saved_symbol_roundtrip_lints_clean(tmp_path):
    net = _mlp()
    path = tmp_path / "mlp-symbol.json"
    net.save(str(path))
    report = lint_json(path.read_text(), shapes={"data": (8, 8)})
    assert not len(report), report.format()


# ------------------------------------------------- donation / collective
def test_da_donated_param_as_label_input():
    mod = _fused_module()
    g = mod._exec_group
    g.label_names = list(g.label_names) + ["fc1_weight"]
    report = lint_module(mod)
    assert report.rules == {"DA203"}


def test_da_shared_cells_with_fused_plan():
    mod = _fused_module()
    mod._exec_group._shared_param_names = {"fc1_weight"}
    report = lint_module(mod)
    assert report.rules == {"DA202"}


def test_da_bucket_buffer_alias():
    sched = BucketScheduler(lambda x: x, lambda k, c, v: None,
                            lambda: 1 << 30)
    buf = np.zeros(4, np.float32)
    sched.note_push_call()
    sched.stage(0, None, buf, priority=1)
    sched.stage(1, None, buf, priority=0)
    report = run_passes(AnalysisContext(sched=sched))
    assert report.rules == {"DA204"}


def test_co_watched_order_mismatch():
    mod = _fused_module()
    mod._exec_group._fused_watched = \
        list(reversed(mod._exec_group._fused_watched))
    report = lint_module(mod)
    assert report.rules == {"CO303"}


def test_co_zero_plan_with_dist_kvstore():
    mod = _fused_module()
    kv = mx.kv.create("dist_sync")
    try:
        from mxnet_tpu.parallel.zero import ZeroPlan
        mod._exec_group._zero_plan = ZeroPlan.__new__(ZeroPlan)
        mod._exec_group._zero_plan.axis = "data"
        mod._exec_group._zero_plan.n = 8
        mod._kvstore = kv
        report = lint_module(mod)
        assert "CO302" in report.rules
    finally:
        mod._kvstore = None
        kv.close()


# ------------------------------------------------------------- host sync
def test_hs_naive_engine(monkeypatch):
    monkeypatch.setenv("MXNET_ENGINE_TYPE", "NaiveEngine")
    net = _mlp()
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8), validate=None)
    from mxnet_tpu.analysis import lint_executor
    report = lint_executor(exe)
    assert report.rules == {"HS501"}


def test_hs_monitor_tap_is_info():
    net = _mlp()
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8), validate=None)
    exe.set_monitor_callback(lambda name, arr: None)
    from mxnet_tpu.analysis import lint_executor
    report = lint_executor(exe)
    assert report.rules == {"HS502"}
    assert report.infos and not report.errors and not report.warnings


# ------------------------------------------------------- retrace / cache
def test_rc_uncacheable_binding():
    net = _mlp()
    exe = net.simple_bind(ctx=mx.cpu(), data=(2, 8), validate=None)
    exe._prog_cache_base = None
    from mxnet_tpu.analysis import lint_executor
    report = lint_executor(exe)
    assert report.rules == {"RC402"}


def test_attr_cache_stable_predicate():
    assert attr_cache_stable(3)[0]
    assert attr_cache_stable("relu")[0]
    assert attr_cache_stable((1, 2, 3))[0]
    assert attr_cache_stable(1.5)[0]
    assert not attr_cache_stable(float("nan"))[0]
    assert not attr_cache_stable(np.arange(2))[0]
    assert not attr_cache_stable(lambda x: x)[0]
    assert not attr_cache_stable(object())[0]


# ------------------------------------------------------ surfaces / modes
def test_bind_validate_raise_mode():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    bad = a + b
    with pytest.raises(mx.MXNetError, match="GV101"):
        bad.bind(mx.cpu(), args={"a": mx.nd.ones((2, 3)),
                                 "b": mx.nd.ones((4, 5))},
                 validate="raise")


def test_bind_validate_warn_mode_logs(caplog):
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    bad = a + b
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.analysis"):
        exe = bad.bind(mx.cpu(), args={"a": mx.nd.ones((2, 3)),
                                       "b": mx.nd.ones((4, 5))},
                       validate="warn")
    assert exe is not None          # warn mode never blocks the bind
    assert any("GV101" in rec.message for rec in caplog.records)


def test_env_validate_mode(monkeypatch):
    monkeypatch.setenv("MXNET_GRAPH_VALIDATE", "raise")
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    with pytest.raises(mx.MXNetError, match="GV101"):
        (a + b).bind(mx.cpu(), args={"a": mx.nd.ones((2, 3)),
                                     "b": mx.nd.ones((4, 5))})
    # per-call override beats the env
    exe = (a + b).bind(mx.cpu(), args={"a": mx.nd.ones((2, 3)),
                                       "b": mx.nd.ones((4, 5))},
                       validate="warn")
    assert exe is not None


def test_lint_disable_suppression(monkeypatch):
    net = _mlp()
    node = net._outputs[0][0]
    node.attrs["debug_buffer"] = np.arange(3)
    monkeypatch.setenv("MXNET_LINT_DISABLE", "RC401")
    assert not len(lint_symbol(net, shapes={"data": (2, 8)}))
    monkeypatch.setenv("MXNET_LINT_DISABLE", "retrace_churn")
    assert not len(lint_symbol(net, shapes={"data": (2, 8)}))
    monkeypatch.setenv("MXNET_LINT_DISABLE", "all")
    assert not len(lint_symbol(net, shapes={"data": (2, 8)}))
    monkeypatch.delenv("MXNET_LINT_DISABLE")
    assert len(lint_symbol(net, shapes={"data": (2, 8)})) == 1


def test_findings_mirror_into_telemetry():
    from mxnet_tpu.telemetry import flightrec, metrics
    mod = _fused_module()
    exe = mod._exec_group.executor
    i1 = exe.arg_names.index("fc1_weight")
    i2 = exe.arg_names.index("fc2_weight")
    exe.arg_arrays[i2] = exe.arg_arrays[i1]
    before = metrics.get_metric("analysis.lint.findings", rule="DA201",
                                severity="error")
    base = before.value if before else 0
    flightrec.clear()
    lint_module(mod)
    after = metrics.get_metric("analysis.lint.findings", rule="DA201",
                               severity="error")
    assert after is not None and after.value == base + 1
    recs = [r for r in flightrec.get_records()
            if r.get("kind") == "lint.finding"]
    assert recs and recs[-1]["rule"] == "DA201"


def test_diagnose_renders_lint_findings(tmp_path):
    """tools/diagnose.py shows lint findings in a crash report."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import diagnose
    finally:
        sys.path.pop(0)
    report = {
        "type": "crash_report", "time": "t", "pid": 1, "where": "bind",
        "ring": [{"kind": "lint.finding", "ts_us": 1, "rule": "DA201",
                  "severity": "error", "node": "fc1_weight",
                  "message": "one buffer is bound twice"}],
        "metrics": {"counters":
                    {'analysis.lint.findings{rule="DA201",'
                     'severity="error"}': 1}},
    }
    path = tmp_path / "crash.json"
    path.write_text(json.dumps(report))
    text = diagnose.render_file(str(path))
    assert "lint findings" in text and "DA201" in text


def test_rule_catalog_consistency():
    """Every rule id used in this file exists; severities are valid."""
    for rule, (sev, title) in RULES.items():
        assert sev in ("info", "warning", "error")
        assert title


# ------------------------------------------------------------ mxlint CLI
def _mxlint_main():
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    try:
        import mxlint
    finally:
        sys.path.pop(0)
    return mxlint.main


def test_mxlint_check_gate(capsys):
    """The CI gate: every bundled model + the two example graphs lint
    clean (exit 0). Runs mxlint in-process so tier-1 pays no second
    interpreter/jax start-up."""
    main = _mxlint_main()
    assert main(["--check"]) == 0
    out = capsys.readouterr().out
    assert "models/resnet20" in out and "examples/dcgan.generator" in out
    assert "0 error(s)" in out


def test_mxlint_json_file_exit_codes(tmp_path, capsys):
    main = _mxlint_main()
    good = _mlp()
    good_path = tmp_path / "good-symbol.json"
    good.save(str(good_path))
    assert main([str(good_path), "--shape", "data=8,8"]) == 0

    bad = {"nodes": [{"op": "_copy", "name": "c",
                      "inputs": [[5, 0, 0]]}],
           "arg_nodes": [], "heads": [[0, 0, 0]]}
    bad_path = tmp_path / "bad-symbol.json"
    bad_path.write_text(json.dumps(bad))
    assert main([str(bad_path)]) == 1          # nonzero on errors
    out = capsys.readouterr().out
    assert "GV106" in out

    # warnings pass by default, fail under --strict
    warn = {"nodes": [
        {"op": "null", "name": "a", "inputs": []},
        {"op": "null", "name": "dead", "inputs": []},
        {"op": "_copy", "name": "c", "inputs": [[0, 0, 0]]}],
        "arg_nodes": [0, 1], "heads": [[2, 0, 0]]}
    warn_path = tmp_path / "warn-symbol.json"
    warn_path.write_text(json.dumps(warn))
    assert main([str(warn_path)]) == 0
    assert main([str(warn_path), "--strict"]) == 1
    assert main([]) == 2                        # nothing to lint


def test_mxlint_rules_listing(capsys):
    main = _mxlint_main()
    assert main(["--rules"]) == 0
    out = capsys.readouterr().out
    for rule in RULES:
        assert rule in out


def test_mxlint_env_audit_gate(capsys):
    """The doc-sync CI gate: zero drift, exit 0."""
    main = _mxlint_main()
    assert main(["--env-audit"]) == 0
    out = capsys.readouterr().out
    assert "0 undocumented, 0 dead rows" in out


def test_mxlint_metric_audit_gate(capsys):
    """The metric-catalog CI gate: zero drift both ways, exit 0."""
    main = _mxlint_main()
    assert main(["--metric-audit"]) == 0
    out = capsys.readouterr().out
    assert "0 undocumented, 0 dead rows" in out


def test_mxlint_memory_plan_cli(capsys):
    """--memory-plan renders a per-policy plan; a tiny capacity trips
    ME801 (exit 1), headroom trips ME802 (info, exit 0)."""
    main = _mxlint_main()
    assert main(["--memory-plan", "resnet20", "--policy", "none",
                 "--policy", "dots", "--batch", "64"]) == 0
    out = capsys.readouterr().out
    assert "memory plan for resnet20" in out and "residuals" in out

    assert main(["--memory-plan", "resnet20", "--batch", "256",
                 "--capacity-gb", "0.05"]) == 1
    out = capsys.readouterr().out
    assert "ME801" in out

    assert main(["--memory-plan", "resnet20", "--batch", "64",
                 "--policy", "all", "--capacity-gb", "4"]) == 0
    out = capsys.readouterr().out
    assert "ME802" in out

    assert main(["--memory-plan", "nosuchmodel"]) == 2


def test_mxlint_precision_audit_cli(capsys):
    """The quant/mixed-precision zoo audits clean through the CLI
    (mlp only here — the full corpus runs under --check in CI)."""
    main = _mxlint_main()
    assert main(["--precision-audit", "--compute-dtype",
                 "float32"]) == 0
    out = capsys.readouterr().out
    assert "models/mlp@float32" in out and "models/mlp@int8" in out


def test_mxlint_mfu_audit_includes_planner_bytes(capsys):
    main = _mxlint_main()
    assert main(["--mfu-audit"]) == 0
    out = capsys.readouterr().out
    assert "planner per-op" in out and "BatchNorm" in out


# ------------------------------------ Pallas kernel validator (PK9xx)
def _dummy_variant(attrs, inputs, aux, is_train, rng):
    return list(inputs), []


def test_fixture_pk901_vmem_overflow():
    """A declared working set past the per-generation VMEM budget
    fails loudly at registration with PK901."""
    op = OpDef("pk901_fixture", lambda *a: ([], []))
    with pytest.raises(mx.MXNetError, match="PK901"):
        op.add_variant("pallas", _dummy_variant, kernel_spec={
            "tiles": [((256, 32768), "float32")] * 2,   # 64 MiB
            "dtypes": ("float32",)})
    assert "pallas" not in op.variants


def test_fixture_pk902_misaligned_tile():
    """Lane (last % 128) and sublane (dtype rows) misalignment both
    fail with PK902."""
    op = OpDef("pk902_fixture", lambda *a: ([], []))
    with pytest.raises(mx.MXNetError, match="PK902"):
        op.add_variant("pallas", _dummy_variant, kernel_spec={
            "tiles": [((8, 100), "float32")], "dtypes": ("float32",)})
    with pytest.raises(mx.MXNetError, match="PK902"):
        op.add_variant("pallas", _dummy_variant, kernel_spec={
            "tiles": [((8, 128), "int8")],     # int8 packs 32 rows
            "dtypes": ("int8",)})


def test_fixture_pk903_dtype_coverage():
    """Empty or gate-uncoverable dtype sets fail with PK903."""
    op = OpDef("pk903_fixture", lambda *a: ([], []))
    with pytest.raises(mx.MXNetError, match="PK903"):
        op.add_variant("pallas", _dummy_variant, kernel_spec={
            "tiles": [((8, 128), "float32")], "dtypes": ()})
    with pytest.raises(mx.MXNetError, match="PK903"):
        op.add_variant("pallas", _dummy_variant, kernel_spec={
            "tiles": [((8, 128), "float32")],
            "dtypes": ("float64",)})


def test_registered_pallas_variants_all_declare_specs():
    """Every shipped production Pallas variant carries a validated
    kernel_spec — an infeasible production kernel can no longer
    register. (User rtc kernels may omit the spec.)"""
    from mxnet_tpu.analysis.kernelcheck import validate_kernel_spec
    from mxnet_tpu.ops.registry import get_op
    shipped = ["SoftmaxOutput", "FusedConvBNReLU", "LayerNorm",
               "FusedBiasGeLU", "Embedding", "sgd_mom_update",
               "adam_update", "QuantizedFullyConnected",
               "QuantizedConvolution", "pallas_sgd_mom_update",
               "pallas_flash_attention", "attention"]
    for name in shipped:
        rec = get_op(name).variants["pallas"]
        spec = rec.get("kernel_spec")
        assert spec is not None, f"{name}:pallas has no kernel_spec"
        validate_kernel_spec(name, "pallas", spec)    # idempotent


def test_valid_kernel_spec_registers():
    op = OpDef("pk_ok_fixture", lambda *a: ([], []))
    op.add_variant("pallas", _dummy_variant, kernel_spec={
        "tiles": [((256, 128), "float32"), ((32, 128), "int8")],
        "dtypes": ("float32", "int8")})
    assert op.variants["pallas"]["kernel_spec"]["dtypes"] == (
        "float32", "int8")


# ------------------------------------------- env-var doc-sync audit
def test_env_audit_in_sync():
    """MXNET_* env reads and docs/env_var.md rows match (the CI gate
    behind ``mxlint --env-audit``)."""
    import os
    from mxnet_tpu.analysis import envaudit
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = envaudit.audit(repo)
    assert not result["undocumented"], result["undocumented"]
    assert not result["dead"], result["dead"]
    # sanity: the scan actually sees the surface, both spellings
    assert "MXNET_GRAPH_VALIDATE" in result["code_vars"]
    assert any(p.startswith("MXNET_RETRY_")
               for p in result["code_prefixes"])


def test_env_audit_detects_drift(tmp_path):
    """A synthetic tree with an undocumented read and a dead row."""
    from mxnet_tpu.analysis import envaudit
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "import os\nX = os.environ.get('MXNET_SECRET_KNOB', '')\n")
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "env_var.md").write_text("* `MXNET_GHOST_KNOB` — unused\n")
    result = envaudit.audit(str(tmp_path))
    assert result["undocumented"] == ["MXNET_SECRET_KNOB"]
    assert result["dead"] == ["MXNET_GHOST_KNOB"]


# --------------------------------------- metric-name doc-sync audit
def test_metric_audit_in_sync():
    """Recorded metric names and the docs/telemetry.md Metric catalog
    match both ways (the CI gate behind ``mxlint --metric-audit``)."""
    import os
    from mxnet_tpu.analysis import metricaudit
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    result = metricaudit.audit(repo)
    assert not result["undocumented"], result["undocumented"]
    assert not result["dead"], result["dead"]
    # sanity: the scan really sees the surface — exact names, the
    # hist= keyword feed, and f-string/metric_prefix families
    assert "module.fit.batches" in result["code_names"]
    assert "executor.compile.seconds" in result["code_names"]
    assert any(p.startswith("serve.decode.")
               for p in result["code_prefixes"])
    assert "step.phase." in result["doc_prefixes"]


def test_metric_audit_detects_drift(tmp_path):
    """A synthetic tree with an unrecorded catalog row and an
    uncatalogued recording, in every resolution mode the scanner
    claims: literal, concatenation, hist= keyword, f-string family."""
    from mxnet_tpu.analysis import metricaudit
    pkg = tmp_path / "mxnet_tpu"
    pkg.mkdir()
    (pkg / "m.py").write_text(
        "from telemetry import counter, gauge, histogram, span\n"
        "def f(key):\n"
        "    counter('secret.items').inc()\n"
        "    name = 'secret.step'\n"
        "    histogram(name + '.seconds').observe(1)\n"
        "    gauge(f'family.{key}').set(1)\n"
        "    span('x', hist='hooked.seconds')\n")
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "telemetry.md").write_text(
        "# Telemetry\n\n"
        "prose mentioning `unrelated.metric` outside the catalog\n\n"
        "## Metric catalog\n\n"
        "| `secret.items` | counter | things |\n"
        "| `ghost.metric` | gauge | recorded by nothing |\n\n"
        "## Next section\n")
    result = metricaudit.audit(str(tmp_path))
    assert result["undocumented"] == ["hooked.seconds", "secret.step.seconds",
                                      "family.*"]
    assert result["dead"] == ["ghost.metric"]
    assert result["ok"] is False

    # adding the missing rows (a `<placeholder>` row covers the
    # f-string family) and dropping the dead one restores sync
    (docs / "telemetry.md").write_text(
        "## Metric catalog\n\n"
        "| `secret.items` | counter | things |\n"
        "| `secret.step.seconds` | histogram | step wall |\n"
        "| `hooked.seconds` | histogram | span feed |\n"
        "| `family.<key>` | gauge | per-key family |\n")
    assert metricaudit.audit(str(tmp_path))["ok"] is True


# --------------------------------------- cost-metadata consistency
def test_every_flops_estimator_has_bytes():
    """The planner and the roofline both fold per-op byte counts: an
    op with flops but no bytes (or vice versa) under-counts one axis
    while looking covered. The registry must have none."""
    from mxnet_tpu.ops.cost import partial_cost_ops
    assert partial_cost_ops() == []


def test_planner_per_op_bytes_cover_cost_ops():
    """The planner's per-op byte table names the ops that dominate the
    resnet20 residual bill, and they all carry cost metadata."""
    from mxnet_tpu import models
    from mxnet_tpu.analysis import memplan
    from mxnet_tpu.ops.registry import get_op
    plan = memplan.plan_symbol(
        models.resnet.get_symbol(10, 20, "3,32,32"),
        {"data": (4, 3, 32, 32)}, policy="none")
    assert plan["per_op_bytes"]
    assert "BatchNorm" in plan["per_op_bytes"]
    for op in plan["per_op_bytes"]:
        assert get_op(op).has_cost(), op


# -------------------------------- registration-time infer validation (S2)
def test_register_validates_infer_shape_arity():
    with pytest.raises(mx.MXNetError, match="badop.*infer_shape"):
        OpDef("badop", lambda *a: ([], []),
              infer_shape=lambda attrs: None)


def test_register_validates_infer_type_arity():
    with pytest.raises(mx.MXNetError, match="badop2.*infer_type"):
        OpDef("badop2", lambda *a: ([], []),
              infer_type=lambda: None)


def test_register_rejects_required_kwonly():
    with pytest.raises(mx.MXNetError, match="keyword-only"):
        OpDef("badop3", lambda *a: ([], []),
              infer_shape=lambda attrs, shapes, *, mode: None)


def test_register_detects_out_known_capability():
    op2 = OpDef("okop2", lambda *a: ([], []),
                infer_shape=lambda attrs, shapes: (shapes, [shapes[0]], []))
    assert op2._infer_accepts_out is False
    op3 = OpDef("okop3", lambda *a: ([], []),
                infer_shape=lambda attrs, shapes, out_known=None:
                (shapes, [shapes[0]], []))
    assert op3._infer_accepts_out is True
    assert OpDef("okop4", lambda *a: ([], [])).shape_passthrough is False
    assert OpDef("okop5", lambda *a: ([], []),
                 shape_passthrough=True).shape_passthrough is True


def test_registered_ops_all_validate():
    """Every op already in the registry satisfies the registration-time
    signature contract (the check ran at import; re-assert explicitly)."""
    from mxnet_tpu.ops.registry import OP_REGISTRY, \
        _validate_infer_signature
    for name, op in OP_REGISTRY.items():
        _validate_infer_signature(name, "infer_shape", op.infer_shape)
        _validate_infer_signature(name, "infer_type", op.infer_type)
