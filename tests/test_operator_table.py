"""The operator table (telemetry/optable.py, mx.profiler.operator_table):
the op index of a compiled program's text, the join with a trace's
events and with the per-node costs, and where an operator meets it
(dump_profile). And the armed step attribution's lagged wait
(executor_group._wait_for_step_before)."""
import json

import numpy as np
import pytest

import jax
import mxnet_tpu as mx
from mxnet_tpu.telemetry import optable
from mxnet_tpu.telemetry import stepattr as sa


def _conv_net(prefix):
    d = mx.sym.Variable("data")
    x = d
    for i in range(2):
        x = mx.sym.Convolution(x, num_filter=8, kernel=(3, 3), pad=(1, 1),
                               no_bias=True, name=f"{prefix}conv{i}")
        x = mx.sym.BatchNorm(x, fix_gamma=False, name=f"{prefix}bn{i}")
        x = mx.sym.Activation(x, act_type="relu", name=f"{prefix}relu{i}")
    x = mx.sym.Pooling(x, global_pool=True, pool_type="avg", kernel=(1, 1),
                       name=f"{prefix}pool")
    x = mx.sym.FullyConnected(x, num_hidden=4, name=f"{prefix}fc")
    return mx.sym.SoftmaxOutput(x, name="softmax")


def _fit(prefix, batches=2, K=1, net=None):
    rs = np.random.RandomState(7)
    X = rs.rand(batches * 8, 3, 8, 8).astype("f")
    y = rs.randint(0, 4, batches * 8).astype("f")
    it = mx.io.NDArrayIter(X, y, batch_size=8)
    mod = mx.mod.Module(net or _conv_net(prefix), context=mx.cpu())
    mx.random.seed(11)
    mod.fit(it, num_epoch=1, steps_per_dispatch=K,
            initializer=mx.initializer.Xavier(),
            optimizer_params={"learning_rate": 0.01, "momentum": 0.9})
    return mod


# ------------------------------------------------------------ the index
def test_index_of_a_fused_step_names_every_node_and_finds_the_phases():
    mod = _fit("ix_")
    assert mod._fused_armed
    assert "fused_step_8x3x8x8" in optable.registered_programs()
    index = optable.program_index("fused_step_8x3x8x8")
    assert index["kind"] == "fused_step" and index["train"]
    assert index["chips"] == 1 and index["steps_per_run"] == 1
    records = index["instructions"].values()
    named = {n for r in records for n in r["nodes"]}
    nodes = {n.name for n in mod.symbol._topo_nodes() if not n.is_variable}
    assert named == nodes
    phases = {r["phase"] for r in records}
    assert {"forward", "backward", "update", "metric"} <= phases
    by_node = {}
    for r in records:
        by_node.setdefault(r["heaviest"], set()).add(r["phase"])
    assert by_node["ix_conv1"] == {"forward", "backward"}
    assert all(r["op"] == "Convolution" for r in records
               if r["heaviest"] == "ix_conv1")
    # the parameters' cast to the compute width and the key split stand
    # outside every node's scope
    assert any(r["phase"] == "unattributed" for r in records)
    # costs ride along, one node at a time
    assert index["costs"]["ix_conv1"]["flops"] == 2 * 8 * 8 * 8 * 8 * 8 * 9
    assert index["costs"]["ix_conv1"]["train_flops"] == \
        3 * index["costs"]["ix_conv1"]["flops"]


def test_index_of_the_scan_program_is_one_loop_with_its_bodys_nodes():
    mod = _fit("sc_", batches=4, K=2)
    name = "scan2_step_8x3x8x8"
    owner, kind, steps = optable.registered_programs()[name]
    assert owner is mod._exec_group and kind == "scan_step" and steps == 2
    index = optable.program_index(name)
    loops = [r for r in index["instructions"].values()
             if r["opcode"] == "while"]
    assert loops and "sc_conv0" in loops[0]["nodes"]
    assert index["nested"]          # the body's instructions


def test_index_of_a_decode_program_carries_its_attention_node():
    from mxnet_tpu.models import transformer as tfm
    dims = dict(vocab_size=32, d_model=16, n_layer=1, n_head=2,
                max_seq_len=8)
    train = mx.mod.Module(tfm.get_symbol(seq_len=8, include_loss=False,
                                         **dims), label_names=[])
    train.bind([("data", (1, 8))], None, for_training=False)
    train.init_params(mx.initializer.Xavier())
    m = mx.mod.Module(tfm.get_decode_symbol(capacity=8, **dims),
                      label_names=[])
    m.bind([("data", (1, 1))], None, for_training=False)
    m.init_params(initializer=None, arg_params=train.get_params()[0],
                  aux_params={}, allow_missing=True)
    d = tfm.KVCacheDecoder(m, capacity=8)
    d.step(np.asarray([[3]], np.int32))
    index = optable.program_index("fwd_infer_1x1")
    assert index["kind"] == "fwd_infer" and not index["train"]
    ops = {r["op"] for r in index["instructions"].values()}
    assert "attention_decode" in ops
    assert {r["phase"] for r in index["instructions"].values()} <= \
        {"forward", "unattributed"}


# A cut of what the v5e's compiler writes (names and metadata as it
# writes them, shapes shortened): a convolution fused with the
# BatchNorm statistics behind it under the BatchNorm's own metadata, the
# weight's layout copy in front of it, a backward convolution, an
# update, an all-reduce, a loop.
_TEXT = '''HloModule jit_fused_step_4x3x8x8, is_scheduled=true, num_partitions=4

%region_0 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[]{:T(128)} parameter(0)
  %b = f32[]{:T(128)} parameter(1)
  ROOT %add.0 = f32[]{:T(128)} add(%a, %b)
}

%fused_computation.1 (p0: bf16[4,8,8,8], p1: bf16[8,8,3,3]) -> (f32[8], bf16[4,8,8,8]) {
  %p0 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = bf16[8,8,3,3]{1,0,3,2:T(8,128)(2,1)} parameter(1)
  %convolution.1 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} convolution(%p0, %p1), window={size=3x3 pad=1_1x1_1}, dim_labels=b01f_oi01->b01f, metadata={op_name="jit(fused_step_4x3x8x8)/jvp(conv1)/conv_general_dilated" stack_frame_id=45}
  %convert.1 = f32[4,8,8,8]{3,2,1,0:T(8,128)} convert(%convolution.1), metadata={op_name="jit(fused_step_4x3x8x8)/jvp(bn1)/convert_element_type" stack_frame_id=47}
  %constant.1 = f32[]{:T(128)} constant(0)
  %reduce.1 = f32[8]{0:T(128)} reduce(%convert.1, %constant.1), dimensions={0,1,2}, to_apply=%region_0, metadata={op_name="jit(fused_step_4x3x8x8)/jvp(bn1)/reduce_sum" stack_frame_id=48}
  ROOT %tuple.1 = (f32[8]{0:T(128)}, bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)}) tuple(%reduce.1, %convolution.1)
}

%fused_computation.2 (p0: bf16[4,8,8,8], p1: bf16[4,8,8,8]) -> f32[8,8,3,3] {
  %p0.1 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %p1.1 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  %multiply.2 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} multiply(%p1.1, %p1.1), metadata={op_name="jit(fused_step_4x3x8x8)/transpose(jvp(relu1))/mul" stack_frame_id=18}
  ROOT %convolution.2 = f32[8,8,3,3]{1,0,3,2:T(8,128)} convolution(%p0.1, %multiply.2), window={size=8x8 pad=1_1x1_1}, dim_labels=f01b_i01o->01bf, metadata={op_name="jit(fused_step_4x3x8x8)/transpose(jvp(conv1))/conv_general_dilated" stack_frame_id=45}
}

%fused_computation.3 (p0: f32[8,8,3,3], p1: f32[8,8,3,3], p2: f32[8,8,3,3]) -> (f32[8,8,3,3], f32[8,8,3,3]) {
  %p0.2 = f32[8,8,3,3]{1,0,3,2:T(8,128)} parameter(0)
  %p1.2 = f32[8,8,3,3]{1,0,3,2:T(8,128)} parameter(1)
  %p2.2 = f32[8,8,3,3]{1,0,3,2:T(8,128)} parameter(2)
  %multiply.3 = f32[8,8,3,3]{1,0,3,2:T(8,128)} multiply(%p1.2, %p2.2), metadata={op_name="jit(fused_step_4x3x8x8)/update/mul" stack_frame_id=70}
  %subtract.3 = f32[8,8,3,3]{1,0,3,2:T(8,128)} subtract(%p0.2, %multiply.3), metadata={op_name="jit(fused_step_4x3x8x8)/update/sub" stack_frame_id=71}
  ROOT %tuple.3 = (f32[8,8,3,3]{1,0,3,2:T(8,128)}, f32[8,8,3,3]{1,0,3,2:T(8,128)}) tuple(%subtract.3, %multiply.3)
}

%body.1 (arg: (s32[], u32[2])) -> (s32[], u32[2]) {
  %arg = (s32[]{:T(128)}, u32[2]{0:T(128)}) parameter(0)
  %gte.b = u32[2]{0:T(128)} get-tuple-element(%arg), index=1
  %xor.b = u32[2]{0:T(128)} xor(%gte.b, %gte.b), metadata={op_name="jit(fused_step_4x3x8x8)/jit(_threefry_split)/while/body/xor"}
  %gte.i = s32[]{:T(128)} get-tuple-element(%arg), index=0
  ROOT %tuple.b = (s32[]{:T(128)}, u32[2]{0:T(128)}) tuple(%gte.i, %xor.b)
}

%cond.1 (arg.1: (s32[], u32[2])) -> pred[] {
  %arg.1 = (s32[]{:T(128)}, u32[2]{0:T(128)}) parameter(0)
  %gte.c = s32[]{:T(128)} get-tuple-element(%arg.1), index=0
  %constant.c = s32[]{:T(128)} constant(5)
  ROOT %compare.c = pred[]{:T(512)} compare(%gte.c, %constant.c), direction=LT
}

ENTRY %main.9 (w__conv1_weight__.1: f32[8,8,3,3], x.1: bf16[4,8,8,8], key.1: u32[2]) -> (f32[8,8,3,3], f32[8]) {
  %w__conv1_weight__.1 = f32[8,8,3,3]{3,2,1,0:T(8,128)} parameter(0), metadata={op_name="w[\\'conv1_weight\\']"}
  %x.1 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} parameter(1)
  %key.1 = u32[2]{0:T(128)} parameter(2)
  %constant.9 = s32[]{:T(128)} constant(0)
  %tuple.9 = (s32[]{:T(128)}, u32[2]{0:T(128)}) tuple(%constant.9, %key.1)
  %while.1 = (s32[]{:T(128)}, u32[2]{0:T(128)}) while(%tuple.9), condition=%cond.1, body=%body.1, metadata={op_name="jit(fused_step_4x3x8x8)/jit(_threefry_split)/while"}
  %copy.5 = f32[8,8,3,3]{1,0,3,2:T(8,128)} copy(%w__conv1_weight__.1), metadata={op_name="w[\\'conv1_weight\\']"}
  %convert_element_type.7 = bf16[8,8,3,3]{1,0,3,2:T(8,128)(2,1)} convert(%copy.5), metadata={op_name="jit(fused_step_4x3x8x8)/jvp()/convert_element_type" stack_frame_id=25}
  %select_reduce_fusion.1 = (f32[8]{0:T(128)}, bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)}) fusion(%x.1, %convert_element_type.7), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(fused_step_4x3x8x8)/jvp(bn1)/reduce_sum" stack_frame_id=48}
  %get-tuple-element.1 = bf16[4,8,8,8]{3,2,1,0:T(8,128)(2,1)} get-tuple-element(%select_reduce_fusion.1), index=1
  %get-tuple-element.2 = f32[8]{0:T(128)} get-tuple-element(%select_reduce_fusion.1), index=0
  %fusion.88 = f32[8,8,3,3]{1,0,3,2:T(8,128)} fusion(%x.1, %get-tuple-element.1), kind=kOutput, calls=%fused_computation.2, metadata={op_name="jit(fused_step_4x3x8x8)/transpose(jvp(conv1))/conv_general_dilated" stack_frame_id=45}
  %all-reduce.3 = f32[8,8,3,3]{1,0,3,2:T(8,128)} all-reduce(%fusion.88), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%region_0, metadata={op_name="jit(fused_step_4x3x8x8)/transpose(jvp(conv1))/conv_general_dilated" stack_frame_id=45}
  %multiply_subtract_fusion.4 = (f32[8,8,3,3]{1,0,3,2:T(8,128)}, f32[8,8,3,3]{1,0,3,2:T(8,128)}) fusion(%copy.5, %all-reduce.3, %all-reduce.3), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(fused_step_4x3x8x8)/update/sub" stack_frame_id=71}
  %get-tuple-element.3 = f32[8,8,3,3]{1,0,3,2:T(8,128)} get-tuple-element(%multiply_subtract_fusion.4), index=0
  ROOT %tuple.10 = (f32[8,8,3,3]{1,0,3,2:T(8,128)}, f32[8]{0:T(128)}) tuple(%get-tuple-element.3, %get-tuple-element.2)
}
'''
_NODE_OPS = {"conv1": "Convolution", "bn1": "BatchNorm",
             "relu1": "Activation"}


def test_a_shared_fusion_is_labelled_by_its_convolution():
    index = optable.op_index(_TEXT, _NODE_OPS)
    assert index["module"] == "jit_fused_step_4x3x8x8"
    assert index["chips"] == 4
    recs = index["instructions"]
    assert sorted(recs) == sorted([
        "while.1", "copy.5", "convert_element_type.7",
        "select_reduce_fusion.1", "fusion.88", "all-reduce.3",
        "multiply_subtract_fusion.4"])
    fused = recs["select_reduce_fusion.1"]
    # its own metadata names the BatchNorm; the convolution inside wins
    assert fused["heaviest"] == "conv1" and fused["op"] == "Convolution"
    assert fused["phase"] == "forward"
    assert fused["nodes"] == ["bn1", "conv1"]
    assert fused["primitive"] == "conv_general_dilated"
    back = recs["fusion.88"]
    assert (back["heaviest"], back["phase"]) == ("conv1", "backward")
    assert back["nodes"] == ["conv1", "relu1"]
    upd = recs["multiply_subtract_fusion.4"]
    assert (upd["phase"], upd["op"], upd["heaviest"], upd["nodes"]) == \
        ("update", "update", "update", [])
    assert recs["all-reduce.3"]["phase"] == "collective"
    assert recs["all-reduce.3"]["heaviest"] == "conv1"
    # no node, no scope: unattributed, with what it was made for
    cast = recs["convert_element_type.7"]
    assert cast["phase"] == "unattributed" and cast["nodes"] == []
    assert cast["operands"] == ["copy.5"]
    assert cast["near"] == {"node": "conv1", "phase": "forward"}
    assert recs["copy.5"]["operands"] == ["w__conv1_weight__.1"]
    # the copy's nearest attributed user is the update, one hop away
    assert recs["copy.5"]["near"] == {"node": "update", "phase": "update"}
    loop = recs["while.1"]
    assert loop["phase"] == "unattributed" and loop["near"] is None
    # the loop's body and condition run as operations of their own
    # inside the loop's event; a fusion's members never do
    assert {"xor.b", "gte.b", "compare.c"} <= index["nested"]
    assert not index["nested"] & (set(recs) | {"convolution.1", "reduce.1"})


# ------------------------------------------------------------ the table
class _Node:
    is_variable = False

    def __init__(self, name, op):
        self.name, self.op = name, op


class _Program:
    """A binding as the registry knows one: it lowers to ``_TEXT`` and
    costs what the test says."""

    class _symbol:
        @staticmethod
        def _topo_nodes():
            return [_Node(n, op) for n, op in _NODE_OPS.items()]

    def lower_program(self, kind):
        assert kind == "fused_step"
        return self

    def compile(self):
        return self

    def as_text(self):
        return _TEXT

    def cost_table(self, train):
        assert train
        return {"per_node": {
            "conv1": {"op": "Convolution", "flops": 4e9, "bytes": 4e6,
                      "train_flops": 12e9, "train_bytes": 12e6,
                      "in_shapes": [(4, 8, 8, 8), (8, 8, 3, 3)]},
            "bn1": {"op": "BatchNorm", "flops": 4e6, "bytes": 8e6,
                    "train_flops": 12e6, "train_bytes": 24e6,
                    "in_shapes": [(4, 8, 8, 8)]}}}


def _ev(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1000, "dur_ns": dur_us * 1000}


def _scripted_events():
    """Two runs of the step on each of two chips (the second chip's are
    longer: the table reads that one), five instructions a run, an
    operation of another program between them and a loop-body
    operation inside the loop's."""
    mod, ops = optable.MODULE_LINE, optable.OP_LINE
    step = "jit_fused_step_4x3x8x8(123456)"
    ev = []
    for plane, stretch in (("/device:TPU:0", 1), ("/device:TPU:1", 2)):
        for run in (0, 1):
            t0 = 1000 + run * 2000
            ev.append(_ev(plane, mod, step, t0, 500 * stretch))
            t = t0
            for name, dur in (
                    ("%while.1 = (s32[], u32[2]) while(%tuple.9)", 10),
                    ("%select_reduce_fusion.1 = (f32[8]) fusion(%x.1)",
                     100 + 20 * run),
                    ("fusion.88", 200), ("all-reduce.3", 40),
                    ("multiply_subtract_fusion.4", 50)):
                ev.append(_ev(plane, ops, name, t, dur * stretch))
                t += dur * stretch
            ev.append(_ev(plane, ops, "xor.b", t0 + 1, 2 * stretch))
        ev.append(_ev(plane, mod, "jit_other(9)", 2600, 50))
        ev.append(_ev(plane, ops, "fusion.88", 2610, 30))
    ev.append(_ev("/host:CPU", "main/1", "fusion.88", 1000, 999))
    return ev


def test_table_over_a_scripted_trace_to_the_digit():
    owner = _Program()
    optable.register_program("fused_step_4x3x8x8", owner, "fused_step")
    optable.register_program("fused_step_9x9", owner, "fused_step")
    table = mx.profiler.operator_table(events=_scripted_events(),
                                       device_kind="TPU v5e")
    # jit_other is nobody's: skipped; fused_step_9x9 never ran: no rows
    assert [p["program"] for p in table["programs"]] == \
        ["fused_step_4x3x8x8"]
    p = table["programs"][0]
    assert p["plane"] == "/device:TPU:1" and p["runs"] == 2
    assert p["chips"] == 4 and p["steps_per_run"] == 1
    assert p["run_ms"] == pytest.approx(1.0)
    rows = {r["instruction"]: r for r in p["rows"]}
    assert [r["instruction"] for r in p["rows"]] == [
        "fusion.88", "select_reduce_fusion.1",
        "multiply_subtract_fusion.4", "all-reduce.3", "while.1"]
    assert rows["fusion.88"]["ms_per_run"] == pytest.approx(0.4)
    assert rows["select_reduce_fusion.1"]["ms_per_run"] == \
        pytest.approx(0.22)
    assert rows["while.1"]["ms_per_run"] == pytest.approx(0.02)
    assert p["nested_ms"] == pytest.approx(0.004)   # xor.b, in the loop's
    assert p["op_ms"] == pytest.approx(0.82)
    assert rows["fusion.88"]["share"] == pytest.approx(0.4 / 0.82)
    assert sum(r["share"] for r in p["rows"]) == pytest.approx(1.0)

    # one chip's quarter of the node's cost; forward | the remainder
    fwd = rows["select_reduce_fusion.1"]
    assert fwd["flops"] == 1e9 and fwd["bytes"] == 1e6
    assert fwd["achieved_tflops"] == pytest.approx(1e9 / 0.22e-3 / 1e12)
    assert fwd["achieved_gbps"] == pytest.approx(1e6 / 0.22e-3 / 1e9)
    assert fwd["bound"] == "compute"
    assert fwd["roofline_pct"] == pytest.approx(
        100 * (1e9 / 197e12) / 0.22e-3)
    # the backward convolution shares (conv1, backward) with the
    # all-reduce's label? no: that row's phase is collective, so the
    # fusion is alone with the remainder of the train factor
    bwd = rows["fusion.88"]
    assert bwd["flops"] == 2e9 and bwd["bytes"] == 2e6
    assert bwd["roofline_pct"] == pytest.approx(
        100 * (2e9 / 197e12) / 0.4e-3)
    assert rows["all-reduce.3"]["flops"] is None
    assert rows["multiply_subtract_fusion.4"]["roofline_pct"] is None

    assert {k: round(v["ms_per_run"], 6) for k, v in p["by_phase"].items()} \
        == {"backward": 0.4, "forward": 0.22, "update": 0.1,
            "collective": 0.08, "unattributed": 0.02}
    assert p["by_phase"]["forward"]["flops"] == 1e9
    by_op = {r["op"]: r for r in p["by_op"]}
    assert by_op["Convolution"]["ms_per_run"] == pytest.approx(0.7)
    assert by_op["Convolution"]["instructions"] == 3
    assert by_op["Convolution"]["flops"] == 3e9
    assert by_op["update"]["ms_per_run"] == pytest.approx(0.1)
    node = p["by_node"][0]
    assert (node["node"], node["phase"], node["op"]) == \
        ("conv1", "backward", "Convolution")
    assert node["achieved_tflops"] == pytest.approx(2e9 / 0.4e-3 / 1e12)
    # every node's cost by op, labelled or not (bn1 labels nothing here)
    assert p["op_costs"]["Convolution"]["flops"] == 3e9
    assert p["op_costs"]["BatchNorm"]["bytes"] == 6e6
    assert p["index_seconds"] >= 0


def test_table_without_peaks_keeps_the_rates_and_drops_the_roofline():
    owner = _Program()
    optable.register_program("fused_step_4x3x8x8", owner, "fused_step")
    p = optable.operator_table(events=_scripted_events(),
                               device_kind="cpu")["programs"][0]
    row = next(r for r in p["rows"] if r["instruction"] == "fusion.88")
    assert row["achieved_tflops"] == pytest.approx(5.0)
    assert row["bound"] is None and row["roofline_pct"] is None


def test_a_dead_binding_leaves_the_registry():
    owner = _Program()
    optable.register_program("fused_step_gone", owner, "fused_step")
    assert "fused_step_gone" in optable.registered_programs()
    del owner
    assert "fused_step_gone" not in optable.registered_programs()
    assert optable.program_index("fused_step_gone") is None
    assert optable.operator_table(events=[])["programs"] == []


# ------------------------------------------------------- dump_profile
def test_dump_profile_carries_the_operators_after_a_traced_fit(tmp_path):
    path = str(tmp_path / "profile.json")
    mx.profiler.profiler_set_config(filename=path)
    mx.profiler.profiler_set_state("run")
    try:
        _fit("dp_")
    finally:
        mx.profiler.profiler_set_state("stop")
    with open(mx.profiler.dump_profile()) as f:
        doc = json.load(f)
    other = doc["otherData"]
    assert other["jax_trace_dir"]
    # the CPU's planes have no XLA Modules line: no program's runs are
    # found, and the table says so with no rows
    assert other["operators"] == {"programs": []}
    assert mx.profiler.operator_table() == {"programs": []}


def test_dump_profile_without_a_jax_trace_is_as_before(tmp_path,
                                                       monkeypatch):
    monkeypatch.setitem(mx.profiler._STATE, "trace_dir", None)
    path = str(tmp_path / "plain.json")
    mx.profiler.profiler_set_config(filename=path)
    with open(mx.profiler.dump_profile()) as f:
        doc = json.load(f)
    assert "operators" not in doc["otherData"]
    assert "jax_trace_dir" not in doc["otherData"]


# ------------------------------------------- the lagged wait, armed
class _Tick:
    """A clock that moves one millisecond a read."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.001
        return self.t


def _step_batches(n):
    rs = np.random.RandomState(3)
    return [mx.io.DataBatch(
        data=[mx.nd.array(rs.rand(8, 3, 8, 8).astype("f"))],
        label=[mx.nd.array(rs.randint(0, 4, 8).astype("f"))])
        for _ in range(n)]


def _bound(prefix):
    mod = mx.mod.Module(_conv_net(prefix), context=mx.cpu())
    mod.bind([("data", (8, 3, 8, 8))], [("softmax_label", (8,))])
    mx.random.seed(5)
    mod.init_params(mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd", optimizer_params={
        "learning_rate": 0.05, "momentum": 0.9})
    assert mod._fused_armed
    return mod


def test_armed_step_waits_for_the_dispatch_before_never_its_own(
        monkeypatch):
    mod = _bound("lw_")
    group = mod._exec_group
    calls, alive = [], []
    prog = group._fused_prog

    def dispatch(*args):
        out = prog(*args)
        alive.append(out[6][0])     # an id is one array's while it lives
        calls.append(("dispatch", id(out[6][0])))    # mets[0]
        return out

    def wait(x):
        calls.append(("wait", id(x)))
        return x

    monkeypatch.setattr(group, "_fused_prog", dispatch)
    monkeypatch.setattr(jax, "block_until_ready", wait)
    prev = sa.use_clock(_Tick())
    sa.configure(armed=True)
    sa.reset()
    try:
        for n, batch in enumerate(_step_batches(4)):
            sa.step_begin(0, n)
            mod.forward_backward(batch)
            mod.update()
            sa.step_end()
        recs = sa.records()
    finally:
        sa.use_clock(prev)
        sa.configure(armed=None)
        sa.reset()
    kinds = [k for k, _ in calls]
    # the first step waits on nothing; every later one waits once,
    # behind its own dispatch, on the dispatch before
    assert kinds == ["dispatch", "dispatch", "wait", "dispatch", "wait",
                     "dispatch", "wait"]
    dispatched = [i for k, i in calls if k == "dispatch"]
    waited = [i for k, i in calls if k == "wait"]
    assert waited == dispatched[:3]
    assert len(set(dispatched)) == 4
    assert len(recs) == 4
    for r in recs:
        assert set(r["phases_us"]) == set(sa.PHASES)
        assert sum(r["phases_us"].values()) == r["wall_us"]
    assert recs[0]["phases_us"]["device"] == recs[1]["phases_us"]["device"]


@pytest.mark.parametrize("K", [1, 2])
def test_ten_armed_steps_equal_ten_unarmed_bit_for_bit(K):
    def run(armed):
        sa.configure(armed=armed)
        try:
            mod = _fit("bb_", batches=10, K=K)
        finally:
            sa.configure(armed=None)
            sa.reset()
        args, aux = mod.get_params()
        return {**{k: v.asnumpy() for k, v in args.items()},
                **{k: v.asnumpy() for k, v in aux.items()}}

    armed, unarmed = run(True), run(False)
    assert armed.keys() == unarmed.keys()
    for k in armed:
        assert np.array_equal(armed[k], unarmed[k]), k
