"""The operator table: device time of a compiled program by Symbol node,
phase and cost.

Three pieces, each usable alone:

* a **program registry** - every compiled program of a binding
  (``Executor``'s ``fwd_infer`` / ``fwd_train`` / ``fwd_bwd``, the
  exec group's ``fused_step`` and K-step ``scan_step``) registers,
  when the binding gets it, its name (``Executor.program_name``, what
  the profiler's ``XLA Modules`` line shows behind ``jit_``) and whom to
  ask to lower it again: one dictionary entry a program, weakly held,
  nothing a step;
* the **op index** of a compiled program's text (``op_index``): per
  instruction of the entry computation, the Symbol nodes it runs
  (``_exec_node`` opens ``jax.named_scope(node.name)``, so every
  instruction's ``op_name`` metadata carries the node in its name
  stack), the phase and the member that labels it;
* the **table** (``operator_table``): the ``XLA Ops`` events of a
  profiler trace summed by instruction inside the runs of each
  registered program, joined to the index and to
  ``mfu.cost_table``'s per-node estimators.

Nothing here runs unless the table is asked for: the index is built
from ``lower().compile().as_text()`` of a program that has already run
(a hit in the persistent compile cache where one is on), never at
bind and never on a step.

**Phases.** From an instruction's name stack: a ``transpose(...)``
wrapper -> ``backward`` (a rematerialised forward inside it included);
a node under ``jvp(...)`` alone or under no wrapper -> ``forward``; the
scopes ``update`` and ``metric`` (the fused step's optimizer loop and
in-step metric counts) -> ``update``, ``metric``; an all-reduce,
all-gather, reduce-scatter, all-to-all or collective-permute ->
``collective``; an instruction none of whose members carries a node or
one of those scopes -> ``unattributed`` (layout copies, the parameter
casts of ``_load_var``, what the compiler hoisted).

**The rule that splits a shared fusion.** A fusion (or a loop, a call,
a conditional) holds the instructions of the computations it calls,
and those may belong to several nodes - a convolution with the
BatchNorm and ReLU behind it. Its row is labelled by ONE member, in
this order: the first ``convolution`` member that carries a node or
scope, else the first ``dot`` that does; else the instruction's own
metadata (the compiler copies the root's there); else the called
computation's ROOT; else the last member in text order that carries a
node or scope. The whole of the instruction's time goes to that
member's node and phase; the other nodes are kept in ``nodes`` and get
none of it. The compiled text gives no weights to split by, and none
are invented.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import time
import weakref

import numpy as np

from . import mfu as _mfu

__all__ = ["register_program", "registered_programs", "parse_hlo",
           "op_index", "program_index", "operator_table", "read_trace"]

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
_SCOPES = ("update", "metric")          # executor_group.py's two scopes
_TRAIN_KINDS = ("fused_step", "scan_step", "fwd_bwd")

_registry = {}      # program name -> (weakref to its owner, kind, steps)


# ------------------------------------------------------------- registry
def register_program(name, owner, kind, steps=1):
    """Note that ``owner.lower_program(kind)`` lowers the program jitted
    under ``name`` again. ``steps``: train steps one run makes (K for
    the K-step scan)."""
    _registry[name] = (weakref.ref(owner), kind, int(steps))


def registered_programs():
    """``{name: (owner, kind, steps)}`` of the programs whose binding is
    still alive; entries of dead bindings are dropped."""
    out = {}
    for name, (ref, kind, steps) in list(_registry.items()):
        owner = ref()
        if owner is None:
            _registry.pop(name, None)
        else:
            out[name] = (owner, kind, steps)
    return out


# ------------------------------------------------------------ HLO text
_COMPUTATION = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s+\(.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s+(.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\b(?:calls|body|condition|to_apply|true_computation|"
                    r"false_computation)=%?([\w.\-]+)")
_CALL_LISTS = re.compile(r"\b(?:branch_computations|called_computations)="
                         r"\{([^}]*)\}")
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute")
#: entry instructions that take no device time of their own
_FREE = frozenset(("parameter", "constant", "get-tuple-element", "tuple",
                   "bitcast"))


_OPERAND = re.compile(r"%([\w.\-]+)")


def _opcode(rest):
    """``(opcode, operand names)`` of an instruction's right-hand side:
    the opcode follows the shape, which is one word or a parenthesised
    tuple of them, and the operands stand in the parentheses behind
    it."""

    def close(text, start):
        depth = 0
        for i in range(start, len(text)):
            depth += text[i] == "("
            depth -= text[i] == ")"
            if depth == 0:
                return i
        return len(text) - 1

    i = close(rest, 0) + 1 if rest.startswith("(") else rest.find(" ")
    tail = rest[i:].lstrip()
    opcode, paren, _ = tail.partition("(")
    if not paren:
        return opcode.strip(), []
    args = tail[len(opcode):close(tail, len(opcode)) + 1]
    return opcode.strip(), _OPERAND.findall(args)


def parse_hlo(text):
    """``{"module", "partitions", "entry", "computations"}`` of a
    compiled module's text: each computation a list of instructions
    ``{"name", "opcode", "operands", "op_name", "root", "calls"}`` in
    text order."""
    head = text.split("\n", 1)[0]
    m = re.match(r"HloModule\s+([\w.\-]+)", head)
    parts = re.search(r"num_partitions=(\d+)", head)
    out = {"module": m.group(1) if m else None,
           "partitions": int(parts.group(1)) if parts else 1,
           "entry": None, "computations": {}}
    current = None
    for line in text.split("\n"):
        if current is None:
            m = _COMPUTATION.match(line)
            if m:
                current = out["computations"].setdefault(m.group(2), [])
                if m.group(1):
                    out["entry"] = m.group(2)
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        rest = m.group(3)
        calls = _CALLS.findall(rest)
        for group in _CALL_LISTS.findall(rest):
            calls += [c.strip().lstrip("%") for c in group.split(",")
                      if c.strip()]
        name = _OP_NAME.search(rest)
        opcode, operands = _opcode(rest)
        current.append({"name": m.group(2), "opcode": opcode,
                        "operands": operands,
                        "op_name": name.group(1) if name else "",
                        "root": bool(m.group(1)), "calls": calls})
    return out


def _components(op_name):
    """A name stack's components: split at the slashes outside
    parentheses."""
    out, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            out.append(op_name[start:i])
            start = i + 1
    out.append(op_name[start:])
    return out


_WRAPPED = re.compile(r"^([\w.\-]+)\((.*)\)$")


def attribute(op_name, node_ops):
    """``(node, phase, primitive)`` of one ``op_name``: the Symbol node
    in its name stack (None where there is none), the phase that stack
    says (None without a node or scope) and the traced primitive, its
    last component."""
    parts = _components(op_name)
    node = scope = None
    backward = False
    for part in parts[:-1] if len(parts) > 1 else parts:
        wrappers = []
        m = _WRAPPED.match(part)
        while m:
            wrappers.append(m.group(1))
            part = m.group(2)
            m = _WRAPPED.match(part)
        if "transpose" in wrappers:
            backward = True
        if "jit" in wrappers or "pjit" in wrappers:
            continue            # jit(<program>), jit(_var): no scope
        if part in node_ops:
            node = node or part
        elif part in _SCOPES and not wrappers:
            scope = scope or part
    if node is not None:
        phase = "backward" if backward else "forward"
    else:
        phase = scope
    return node, phase, parts[-1]


def _members(comps, inst, seen=None):
    """The instructions of every computation ``inst`` calls, nested
    calls included, in text order."""
    seen = set() if seen is None else seen
    out = []
    for name in inst["calls"]:
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for member in comps[name]:
            out.append(member)
            if member["calls"]:
                out += _members(comps, member, seen)
    return out


def op_index(text, node_ops):
    """The op index of one compiled module.

    ``node_ops`` maps the graph's compute-node names to their MXNet ops.
    Returns ``{"module", "chips", "instructions", "nested"}``:
    ``instructions`` has a record for every instruction of the entry
    computation that can take device time - ``{"instruction",
    "opcode", "primitive", "operands", "nodes", "op", "phase",
    "heaviest", "near"}``, where ``heaviest`` is the node (or scope) of
    the member that labels it by the module docstring's rule, ``op``
    that node's MXNet op (the scope's name for ``update`` / ``metric``),
    ``primitive`` the traced primitive of that member and ``operands``
    what the instruction reads (through tuple elements and bitcasts).
    ``near`` is a hint for a reader and feeds no sum: for an
    ``unattributed`` instruction, the node and phase of the nearest
    attributed instruction that uses its result - what a layout copy
    or a cast was made for. ``nested`` holds the names of the
    instructions inside loop bodies, branches and calls, whose trace
    events lie inside their caller's."""
    hlo = parse_hlo(text)
    comps = hlo["computations"]
    entry = comps.get(hlo["entry"], [])
    alias = {i["name"]: i["operands"][0] for i in entry
             if i["opcode"] in ("get-tuple-element", "bitcast")
             and i["operands"]}

    def producer(name):
        while name in alias:
            name = alias[name]
        return name

    records, nested, users = {}, set(), {}
    for inst in entry:
        if inst["opcode"] in _FREE:
            continue
        operands = [producer(o) for o in inst["operands"]]
        for o in operands:
            users.setdefault(o, []).append(inst["name"])
        members = _members(comps, inst)
        if inst["opcode"] != "fusion":
            # a loop's or a branch's instructions run as operations of
            # their own, inside their caller's event
            nested.update(m["name"] for m in members)
        tagged = [(m, attribute(m["op_name"], node_ops))
                  for m in [inst] + members if m["op_name"]]
        tagged = [(m, a) for m, a in tagged if a[1] is not None]
        nodes = []
        for _m, (node, _phase, _prim) in tagged:
            if node is not None and node not in nodes:
                nodes.append(node)
        label = None
        for opcode in ("convolution", "dot"):
            label = label or next((a for m, a in tagged
                                   if m["opcode"] == opcode), None)
        if label is None:
            own = next((a for m, a in tagged if m is inst), None)
            root = next((a for m, a in tagged if m["root"]), None)
            label = own or root or (tagged[-1][1] if tagged else None)
        node, phase, primitive = label or (None, None, None)
        heaviest = node if node is not None else phase
        op = node_ops.get(node, phase)
        if _COLLECTIVE.search(inst["opcode"]) or \
                _COLLECTIVE.search(inst["name"]):
            phase = "collective"
        if primitive is None and inst["op_name"]:
            primitive = _components(inst["op_name"])[-1]
        records[inst["name"]] = {
            "instruction": inst["name"], "opcode": inst["opcode"],
            "primitive": primitive, "operands": operands[:4],
            "nodes": nodes, "op": op, "phase": phase or "unattributed",
            "heaviest": heaviest, "near": None}
    for rec in records.values():
        if rec["phase"] != "unattributed":
            continue
        frontier, seen = [rec["instruction"]], set()
        for _hop in range(6):
            frontier = [u for name in frontier for u in users.get(name, [])
                        if u not in seen]
            seen.update(frontier)
            hit = next((records[u] for u in frontier
                        if records[u]["phase"] != "unattributed"), None)
            if hit is not None:
                rec["near"] = {"node": hit["heaviest"],
                               "phase": hit["phase"]}
            if hit is not None or not frontier:
                break
    return {"module": hlo["module"], "chips": hlo["partitions"],
            "instructions": records, "nested": nested}


def program_index(name):
    """The op index of the registered program ``name`` with its per-node
    costs (``mfu.cost_table``'s ``per_node``, bytes at the binding's
    compute width), or None where no live binding registered it:
    lowers and compiles the program again
    (``owner.lower_program(kind)``) and reads the compiled text.
    ``seconds`` is what that took."""
    entry = registered_programs().get(name)
    if entry is None:
        return None
    owner, kind, steps = entry
    t0 = time.perf_counter()
    text = owner.lower_program(kind).compile().as_text()
    exe = getattr(owner, "executor", owner)
    node_ops = {n.name: n.op for n in exe._symbol._topo_nodes()
                if not n.is_variable}
    index = op_index(text, node_ops)
    train = kind in _TRAIN_KINDS
    table = exe.cost_table(train=train) or {}
    # ops/cost.py counts 4 bytes an element; a binding with a compute
    # dtype moves its activations at that width
    width = getattr(exe, "_compute_dtype", None)
    scale = np.dtype(width).itemsize / 4.0 if width is not None else 1.0
    costs = {node: dict(c, bytes=c["bytes"] * scale,
                        train_bytes=c["train_bytes"] * scale)
             for node, c in table.get("per_node", {}).items()}
    index.update(program=name, kind=kind, steps_per_run=steps, train=train,
                 costs=costs, seconds=time.perf_counter() - t0)
    return index


# --------------------------------------------------------------- trace
def read_trace(trace_dir):
    """Flat events ``{"plane", "line", "name", "start_ns", "dur_ns"}``
    of the newest ``.xplane.pb`` under ``trace_dir``: the device planes'
    ``XLA Modules`` and ``XLA Ops`` lines."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        return []
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (MODULE_LINE, OP_LINE):
                continue
            for ev in line.events:
                if ev.duration_ns > 0:
                    out.append({"plane": plane.name, "line": line.name,
                                "name": ev.name, "start_ns": ev.start_ns,
                                "dur_ns": ev.duration_ns})
    return out


def _instruction_name(event_name):
    """An operation's trace name is its instruction's name or its whole
    HLO line."""
    return event_name.split(" = ")[0].strip().lstrip("%")


def _median(values):
    values = sorted(values)
    n = len(values)
    return (values[n // 2] + values[(n - 1) // 2]) / 2.0


def _program_runs(events, name):
    """``(plane, [(start, end)...])`` of the runs of program ``name`` on
    the chip whose median run is longest, or ``(None, [])``; a run
    shorter than four fifths of the median (cut by the trace's start)
    is left out."""
    per_plane = {}
    for e in events:
        if e["line"] == MODULE_LINE and e["plane"].startswith("/device:") \
                and e["name"].split("(")[0] == "jit_" + name:
            per_plane.setdefault(e["plane"], []).append(
                (e["start_ns"], e["start_ns"] + e["dur_ns"]))
    if not per_plane:
        return None, []
    plane = max(sorted(per_plane), key=lambda p: _median(
        [b - a for a, b in per_plane[p]]))
    runs = sorted(per_plane[plane])
    # a trace that starts inside a run holds the run's tail alone: it is
    # no run to divide by
    whole = 0.8 * _median([b - a for a, b in runs])
    return plane, [(a, b) for a, b in runs if b - a >= whole]


def _op_ns_by_instruction(events, plane, runs):
    """Summed ``XLA Ops`` time by instruction name, of the events of
    ``plane`` that start inside one of ``runs``."""
    starts = [a for a, _b in runs]
    out = {}
    for e in events:
        if e["plane"] != plane or e["line"] != OP_LINE:
            continue
        i = bisect.bisect_right(starts, e["start_ns"]) - 1
        if i >= 0 and e["start_ns"] < runs[i][1]:
            name = _instruction_name(e["name"])
            out[name] = out.get(name, 0) + e["dur_ns"]
    return out


# ---------------------------------------------------------------- costs
def _cost_fields(flops, nbytes, ms, peaks):
    """Achieved rates and the roofline share of ``flops`` and ``nbytes``
    done in ``ms``: the bound is whichever least time is longer, the
    share that least time over the measured one. Never clipped: the
    byte counts are ``ops/cost.py``'s, one read of every input and one
    write of every output of the unfused op, so a reading over 100 on
    a memory-bound row says the compiler fused reads away that the
    estimator counts, not that the chip beat its peak."""
    out = {"flops": flops, "bytes": nbytes, "achieved_tflops": None,
           "achieved_gbps": None, "bound": None, "roofline_pct": None}
    if flops is None or not ms:
        return out
    seconds = ms / 1e3
    out["achieved_tflops"] = flops / seconds / 1e12
    out["achieved_gbps"] = nbytes / seconds / 1e9
    peak_flops, peak_bw = peaks
    if peak_flops and peak_bw:
        t_flops, t_bytes = flops / peak_flops, nbytes / peak_bw
        out["bound"] = "compute" if t_flops >= t_bytes else "memory"
        out["roofline_pct"] = 100.0 * max(t_flops, t_bytes) / seconds
    return out


def _phase_cost(cost, phase, chips):
    """One chip's FLOPs and bytes of a node in a phase: the forward
    estimate forward, the train factor's remainder backward."""
    if cost is None or phase not in ("forward", "backward"):
        return None, None
    if phase == "forward":
        flops, nbytes = cost["flops"], cost["bytes"]
    else:
        flops = cost["train_flops"] - cost["flops"]
        nbytes = cost["train_bytes"] - cost["bytes"]
    return flops / chips, nbytes / chips


def _variant(cost, decisions):
    """The kernel tier's variant for a node, where its audit log
    (``decisions``) holds one for the node's op at the node's input
    shapes."""
    if cost is None:
        return None
    shapes = [list(s) for s in cost["in_shapes"]]
    for d in decisions:
        if d.get("op") == cost["op"] and \
                [list(s) for s in d.get("shapes", [])][:len(shapes)] \
                == shapes:
            return d.get("variant")
    return None


def _rollup(rows, key, peaks):
    """``[(key, group)...]`` of ``rows`` summed by ``key(row)``, longest
    first; a group's cost is the sum of its (node, phase)s', each
    counted once."""
    groups = {}
    for row in rows:
        g = groups.setdefault(key(row), {
            "op": row["op"], "ms_per_run": 0.0, "share": 0.0,
            "instructions": 0, "_costs": {}})
        g["ms_per_run"] += row["ms_per_run"]
        g["share"] += row["share"]
        g["instructions"] += 1
        if row["_group_cost"] is not None:
            g["_costs"][(row["heaviest"], row["phase"])] = \
                row["_group_cost"]
    out = []
    for k, g in groups.items():
        costs = list(g.pop("_costs").values())
        flops = sum(c[0] for c in costs) if costs else None
        nbytes = sum(c[1] for c in costs) if costs else None
        out.append((k, dict(g, **_cost_fields(flops, nbytes,
                                              g["ms_per_run"], peaks))))
    out.sort(key=lambda kv: -kv[1]["ms_per_run"])
    return out


def _program_table(events, name, steps, peaks):
    plane, runs = _program_runs(events, name)
    if not runs:
        return None
    by_name = _op_ns_by_instruction(events, plane, runs)
    index = program_index(name)
    per_run = 1e6 * len(runs) * steps           # ns -> ms a step
    chips = index["chips"]
    rows, nested_ms = [], 0.0
    for inst, ns in by_name.items():
        if inst in index["nested"]:
            nested_ms += ns / per_run
            continue
        rec = index["instructions"].get(inst) or {
            "instruction": inst, "opcode": None, "primitive": None,
            "operands": [], "nodes": [], "op": None,
            "phase": "unattributed", "heaviest": None, "near": None}
        rows.append(dict(rec, ms_per_run=ns / per_run))
    total = sum(r["ms_per_run"] for r in rows) or 1.0
    # a (node, phase)'s cost belongs to the instructions it labels
    # together: a row carries it only where it is the only one
    labelled = {}
    for r in rows:
        r["share"] = r["ms_per_run"] / total
        labelled.setdefault((r["heaviest"], r["phase"]), []).append(r)
    from .. import kernel_tier
    decisions = kernel_tier.decisions()
    variants = {}
    for (node, phase), group in labelled.items():
        cost = index["costs"].get(node)
        flops, nbytes = _phase_cost(cost, phase, chips)
        variants[node] = _variant(cost, decisions)
        sole = len(group) == 1
        for r in group:
            r["_group_cost"] = None if flops is None else (flops, nbytes)
            r.update(_cost_fields(flops if sole else None,
                                  nbytes if sole else None,
                                  r["ms_per_run"], peaks))
            r["variant"] = variants[node]
    rows.sort(key=lambda r: -r["ms_per_run"])

    by_node = [dict(g, node=node, phase=phase, variant=variants[node])
               for (node, phase), g in _rollup(
                   rows, lambda r: (r["heaviest"], r["phase"]), peaks)]
    by_op = [g for _op, g in _rollup(rows, lambda r: r["op"], peaks)]
    by_phase = {}
    for phase, g in _rollup(rows, lambda r: r["phase"], peaks):
        del g["op"]
        by_phase[phase] = g
    for r in rows:
        del r["_group_cost"]
    # every node's cost by op, labelled or not: what a reader divides
    # an op's time into
    key = "train_" if index["train"] else ""
    op_costs = {}
    for cost in index["costs"].values():
        c = op_costs.setdefault(cost["op"], {"flops": 0.0, "bytes": 0.0})
        c["flops"] += cost[key + "flops"] / chips
        c["bytes"] += cost[key + "bytes"] / chips
    durs = [b - a for a, b in runs]
    return {"program": name, "kind": index["kind"], "plane": plane,
            "runs": len(runs), "steps_per_run": steps, "chips": chips,
            "run_ms": sum(durs) / len(durs) / 1e6 / steps,
            "run_ms_median": _median(durs) / 1e6 / steps,
            "op_ms": sum(r["ms_per_run"] for r in rows),
            "nested_ms": nested_ms, "index_seconds": index["seconds"],
            "rows": rows, "by_node": by_node, "by_op": by_op,
            "by_phase": by_phase, "op_costs": op_costs}


def operator_table(events=None, trace_dir=None, device_kind=None):
    """Device time by Symbol node, phase and cost of every registered
    program that ran in a profiler trace.

    ``events`` are flat events ``{"plane", "line", "name", "start_ns",
    "dur_ns"}`` (``read_trace``'s shape); without them the newest
    ``.xplane.pb`` under ``trace_dir`` is read. For every registered
    program whose name stands on an ``XLA Modules`` line (as
    ``jit_<name>(<fingerprint>)``) the ``XLA Ops`` events that start
    inside its runs are summed by instruction, on the chip whose runs
    are longest, divided by the runs (times K for the K-step scan) and
    joined to the program's op index. Returns ``{"programs": [...]}``,
    each with

    ``rows``      one per instruction, longest first: ``ms_per_run``,
                  ``share`` (of the program's operation time), the
                  index's ``op``, ``nodes``, ``phase``, ``heaviest``,
                  ``opcode``, ``primitive``, the kernel tier's
                  ``variant`` where its log has the site, and - where
                  the instruction is the only one its (node, phase)
                  labels - ``flops``, ``bytes`` (one chip's; the
                  forward estimate for ``forward``, the train factor's
                  remainder for ``backward``), ``achieved_tflops``,
                  ``achieved_gbps``, ``bound`` and ``roofline_pct``
                  against ``mfu.device_peaks``
    ``by_node``   the same summed by (node, phase), where the cost is
                  whole; ``by_op`` by MXNet op; ``by_phase`` by phase
    ``op_costs``  one chip's FLOPs and bytes of ALL the nodes of an op
                  (train totals for a train program), labelled or not
    ``run_ms``    mean device time of a run, a step; ``op_ms`` the rows'
                  sum; ``nested_ms`` operations inside loop bodies,
                  already inside their loop's row
    ``index_seconds``  what lowering, compiling and reading took

    A module no live binding registered is skipped; a registered
    program with no run in the trace gives no entry and is not lowered.
    """
    if events is None:
        events = read_trace(trace_dir) if trace_dir else []
    peaks = _mfu.device_peaks(device_kind)
    on_line = {e["name"].split("(")[0] for e in events
               if e["line"] == MODULE_LINE}
    programs = []
    for name, (_owner, _kind, steps) in sorted(
            registered_programs().items()):
        if "jit_" + name not in on_line:
            continue
        table = _program_table(events, name, steps, peaks)
        if table is not None:
            programs.append(table)
    return {"programs": programs}
