"""Device time of the train step by the graph's own nodes: what the
program's operator table (``mx.profiler.operator_table``, PR 50) says
of the traced run's events - per-step device ms by phase (forward,
backward, update, metric, collective, unattributed) and by MXNet op,
on the chip ``step.device_ms`` reads. For the readers of the
``step.*_ms`` metrics and ``conv_train_roofline``; a program without
the table (every tree before PR 50) or a trace without a registered
step program gives None, and the metric is left out of the line.

By hand: with ``CHIPBENCH_TRACE_DUMP`` set, the whole table is written
there as ``operator_table.<program>.json``."""
from __future__ import annotations

import json
import os

from . import common, costs

_STEP_KINDS = ("fused_step", "scan_step")
#: the ops whose time is matmul time
CONV_OPS = ("Convolution", "FullyConnected")


def step_table(obs):
    """The operator table of the run's train-step program (the one
    that held the chip longest), or None. Built once a run: the
    program lowers and compiles its step again for it (a hit in the
    compile cache), and the line ``operator_table`` says what that
    took."""
    if "_step_table" in obs:
        return obs["_step_table"]
    obs["_step_table"] = None
    events = obs.get("events")
    if not events:
        return None
    from mxnet_tpu import profiler
    build = getattr(profiler, "operator_table", None)
    if build is None:
        return None
    programs = [p for p in build(events=events,
                                 device_kind=obs.get("device_kind"))
                ["programs"] if p["kind"] in _STEP_KINDS and p["rows"]]
    if not programs:
        return None
    table = max(programs, key=lambda p: p["run_ms"] * p["runs"])
    obs["_step_table"] = table
    walls = sorted(r["wall_us"] / max(1, r.get("steps", 1)) / 1e3
                   for r in obs.get("stepattr") or [])
    common.say("operator_table", program=table["program"],
               step_wall_ms={"steps": len(walls),
                             "median": walls[len(walls) // 2],
                             "largest": walls[-3:],
                             "sum": sum(walls)} if walls else None,
               plane=table["plane"], runs=table["runs"],
               chips=table["chips"], run_ms=table["run_ms"],
               op_ms=table["op_ms"], nested_ms=table["nested_ms"],
               index_seconds=table["index_seconds"],
               instructions=len(table["rows"]),
               by_phase={k: v["ms_per_run"]
                         for k, v in table["by_phase"].items()},
               by_op={r["op"] or "none": r["ms_per_run"]
                      for r in table["by_op"]},
               unattributed_top=[
                   [r["instruction"], r["ms_per_run"], r["opcode"],
                    r["operands"][:2], r["near"]]
                   for r in table["rows"]
                   if r["phase"] == "unattributed"][:5])
    dump = os.environ.get("CHIPBENCH_TRACE_DUMP")
    if dump:
        os.makedirs(dump, exist_ok=True)
        with open(os.path.join(dump, "operator_table."
                               + table["program"] + ".json"), "w") as f:
            json.dump(table, f, indent=1)
    return table


def phase_ms(obs, phase):
    """Device ms a step in the instructions of one phase; 0.0 where the
    table has no such instruction."""
    table = step_table(obs)
    if table is None:
        return None
    row = table["by_phase"].get(phase)
    return 0.0 if row is None else row["ms_per_run"]


def op_ms(obs, ops=CONV_OPS):
    """Device ms a step in the instructions whose labelling member
    belongs to a node of one of ``ops``, every phase."""
    table = step_table(obs)
    if table is None:
        return None
    return sum(r["ms_per_run"] for r in table["by_op"] if r["op"] in ops)


def op_roofline(obs, ops=CONV_OPS):
    """Least time of ``ops``' nodes' train FLOPs (the program's own
    ``mfu.cost_table``, one chip's share) at the peak of
    ``peaks.json``, over ``op_ms``, in percent. Never clipped."""
    table = step_table(obs)
    ms = op_ms(obs, ops)
    if table is None or not ms:
        return None
    flops = sum(table["op_costs"].get(op, {}).get("flops", 0.0)
                for op in ops)
    peak = costs.peaks(obs["device_kind"])["bf16_flops_per_s"]
    return 100.0 * (flops / peak * 1e3) / ms
