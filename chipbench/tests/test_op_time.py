"""Quick checks of ``chipbench/op_time.py`` and the six layer files
over it (``step.forward_ms`` ... ``conv_train_roofline``): a program
without the operator table reads as nothing, a scripted table reads to
the digit, and a slice recorded on the v5e (``testdata/
v5e_fit_1chip_optable_slice.json``: the head of a traced
``resnet50-fit-1chip`` run's events beside the op index's records of
their instructions, by hand from ``CHIPBENCH_TRACE_DUMP``) goes through
the program's own join."""
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import manifest, op_time, readers, trace  # noqa: E402

NEW = ("step.forward_ms", "step.backward_ms", "step.update_ms",
       "step.conv_ms", "conv_train_roofline", "step.unattributed_ms")


def _metrics():
    cell = manifest.resolve(manifest.load(), "resnet50-fit-1chip")
    found = {m.name: m for m in cell.per_layer if m.name in NEW}
    assert sorted(found) == sorted(NEW)
    return found


def _read_all(obs):
    return {name: readers.read(m, dict(obs))
            for name, m in _metrics().items()}


def _group(ms, flops=None):
    return {"ms_per_run": ms, "share": 0.0, "instructions": 1,
            "flops": flops, "bytes": None}


def _scripted_table():
    return {"programs": [
        {"program": "fwd_infer_8x1", "kind": "fwd_infer", "runs": 900,
         "run_ms": 5.0, "rows": [{}], "by_phase": {}, "by_op": []},
        {"program": "fused_step_256x3x224x224", "kind": "fused_step",
         "plane": "/device:TPU:0", "runs": 20, "chips": 1,
         "steps_per_run": 1, "run_ms": 100.0, "op_ms": 98.0,
         "nested_ms": 0.0, "index_seconds": 1.5,
         "rows": [{"instruction": "copy.1", "ms_per_run": 3.0,
                   "opcode": "copy", "operands": ["w.1"], "near": None,
                   "phase": "unattributed"}],
         "by_phase": {"forward": _group(30.0), "backward": _group(60.0),
                      "update": _group(5.0), "unattributed": _group(3.0)},
         "by_op": [dict(_group(50.0), op="Convolution"),
                   dict(_group(30.0), op="BatchNorm"),
                   dict(_group(2.0), op="FullyConnected"),
                   dict(_group(5.0), op="update"),
                   dict(_group(3.0), op=None)],
         "op_costs": {"Convolution": {"flops": 5.0e12, "bytes": 1e9},
                      "FullyConnected": {"flops": 0.122e12, "bytes": 1e6},
                      "BatchNorm": {"flops": 1e10, "bytes": 1e10}}}]}


def test_a_program_without_the_table_reads_as_nothing(monkeypatch):
    """Every tree before PR 50, an untraced run, a trace in which no
    registered step program ran: None from all six, nothing raised."""
    from mxnet_tpu import profiler
    events = [{"plane": "/device:TPU:0", "line": trace.MODULE_LINE,
               "name": "jit_fused_step_1x1(7)", "start_ns": 0,
               "dur_ns": 10}]
    obs = {"events": events, "device_kind": "TPU v5 lite", "chips": 1}
    assert set(_read_all(obs).values()) == {None}   # nobody registered
    assert set(_read_all(dict(obs, events=None)).values()) == {None}
    monkeypatch.delattr(profiler, "operator_table")
    assert set(_read_all(obs).values()) == {None}


def test_the_six_layer_files_read_a_scripted_table(monkeypatch, capsys):
    from mxnet_tpu import profiler
    monkeypatch.setattr(profiler, "operator_table",
                        lambda events, device_kind: _scripted_table())
    obs = {"events": [{}], "device_kind": "TPU v5 lite", "chips": 1}
    got = _read_all(obs)
    assert got["step.forward_ms"] == 30.0
    assert got["step.backward_ms"] == 60.0
    assert got["step.update_ms"] == 5.0
    assert got["step.unattributed_ms"] == 3.0
    assert got["step.conv_ms"] == 52.0
    # 5.122 TFLOP at 197 TFLOP/s = 26.0 ms least, over 52 ms
    assert got["conv_train_roofline"] == pytest.approx(
        100 * (5.122e12 / 197e12 * 1e3) / 52.0)
    # a phase the table lacks reads 0, not None
    assert op_time.phase_ms(dict(obs), "collective") == 0.0
    line = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if '"operator_table"' in ln][0]
    assert line["program"] == "fused_step_256x3x224x224"
    assert line["index_seconds"] == 1.5
    assert line["unattributed_top"][0][:2] == ["copy.1", 3.0]


def _records(packed):
    """The op index's records from the testdata's rows over its string
    table (its note has the order; None is -1)."""
    text = packed["strings"]

    def s(i):
        return None if i < 0 else text[i]

    out = {}
    for name, opcode, prim, operands, nodes, op, phase, heavy, nn, nph \
            in packed["rows"]:
        out[s(name)] = {
            "instruction": s(name), "opcode": s(opcode),
            "primitive": s(prim), "operands": [s(o) for o in operands],
            "nodes": [s(n) for n in nodes], "op": s(op), "phase": s(phase),
            "heaviest": s(heavy),
            "near": None if nn < 0 else {"node": s(nn), "phase": s(nph)}}
    return out


def test_recorded_fit_slice_goes_through_the_programs_join(monkeypatch):
    """The head of a traced fit-1chip run on the v5e: the program's
    ``operator_table`` joins the recorded events to the recorded index
    (what ``program_index`` read from the compiled text on the chip),
    and the readers give what they gave when recorded."""
    from mxnet_tpu.telemetry import optable
    with open(os.path.join(ROOT, "chipbench", "testdata",
                           "v5e_fit_1chip_optable_slice.json")) as f:
        rec = json.load(f)
    index = dict(rec["index"], nested=set(rec["index"]["nested"]),
                 instructions=_records(rec["index"]["instructions"]))

    class Owner:            # the registry holds its owner weakly
        pass

    owner = Owner()
    optable.register_program(index["program"], owner, index["kind"])
    monkeypatch.setattr(optable, "program_index", lambda name: index)
    obs = {"events": trace.unpack(rec["events"]),
           "device_kind": rec["device_kind"], "chips": 1}
    got = _read_all(obs)
    for name, want in rec["expect"]["metrics"].items():
        assert got[name] == pytest.approx(want, rel=1e-9), name
    table = op_time.step_table(dict(obs))
    assert table["runs"] == rec["expect"]["runs"]
    phases = sum(g["ms_per_run"] for g in table["by_phase"].values())
    assert phases == pytest.approx(table["op_ms"])
    # the honesty check: most of the step has a name
    assert got["step.unattributed_ms"] < 0.25 * table["op_ms"]
    assert 0 < got["conv_train_roofline"] < 100
    assert got["step.conv_ms"] < got["step.forward_ms"] \
        + got["step.backward_ms"]
