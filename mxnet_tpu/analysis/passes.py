"""Bind-time static-analysis passes over the bound graph.

The reference validated every graph with iterative NNVM passes
(InferShape/InferType, graph_executor.cc:425) *before* anything
executed. This module re-grows that discipline for the hazards this
framework actually has: donated fused/scan buffers, in-program
collective plans, ready-order bucket all-reduces, program-cache
keys, dtype flow through the mixed-precision/int8 tiers
(``precision_flow``/QT7xx, precision.py), and predicted-OOM memory
plans (``memory_planner``/ME8xx, memplan.py — inert unless armed). Each pass walks the Symbol node graph plus whatever execution
state is available (a bound Executor, an armed exec group's fused/scan
plan, a kvstore bucket scheduler) and emits structured diagnostics —
finding at bind time what PR 2's runtime NaN-poison and crash dumps
only catch at step 40k on a pod.

Passes are pure observers: they never mutate the graph, never dispatch
device work, and a pass that itself fails must never break a bind — a
crash inside a pass becomes an ``XX001`` info finding.

Entry points:

* ``lint_symbol(sym, shapes)`` / ``lint_executor(exe)`` /
  ``lint_module(mod)`` / ``lint_json(text)`` — build a context and run
  every applicable pass, returning a :class:`Report`;
* ``validate_executor(exe, mode)`` / ``validate_module(mod, mode)`` —
  the bind-time hooks behind ``bind(validate=...)`` and
  ``MXNET_GRAPH_VALIDATE`` (warn -> log, raise -> MXNetError on
  error-severity findings);
* findings mirror into the telemetry registry
  (``analysis.lint.findings`` counters) and the flight-recorder ring
  (``lint.finding`` records) so ``tools/diagnose.py`` reports them.

Suppression: ``MXNET_LINT_DISABLE`` takes a comma-separated list of
rule ids (``GV107,HS501``), pass names (``host_sync``), or ``all``.
"""
from __future__ import annotations

import json as _json
import logging
import os
from collections import OrderedDict

import numpy as np

from ..base import MXNetError
from ..program_cache import attr_cache_stable
from .diagnostics import Diagnostic, Report

__all__ = ["AnalysisContext", "PASSES", "run_passes", "lint_symbol",
           "lint_executor", "lint_module", "lint_json",
           "validate_executor", "validate_module", "resolve_mode",
           "attr_cache_stable"]

log = logging.getLogger(__name__)


class AnalysisContext:
    """Everything a pass may look at; absent fields disable the checks
    that need them (static analysis is best-effort by design)."""

    def __init__(self, symbol=None, known_shapes=None, executor=None,
                 exec_group=None, module=None, kvstore=None, sched=None,
                 json_graph=None, assume_multiworker=False,
                 compute_dtype=None, memplan=None):
        self.symbol = symbol
        self.known_shapes = dict(known_shapes or {})
        self.executor = executor
        self.exec_group = exec_group
        self.module = module
        self.kvstore = kvstore
        self.sched = sched            # kvstore_sched.BucketScheduler
        self.json_graph = json_graph  # raw dict of a symbol JSON
        # single-process runs can't diverge across workers; fixtures and
        # mxlint set this to audit a plan as if it ran on a multihost mesh
        self.assume_multiworker = assume_multiworker
        # precision_flow: simulate a mixed-precision binding; bound
        # executors contribute their own _compute_dtype when unset
        self.compute_dtype = compute_dtype
        # memory_planner: options dict ({"capacity_bytes":..., "policy":
        # ..., "buckets":...}); None (the default) keeps the planner
        # inert so bind-time lint stays inside the <2% overhead gate —
        # mxlint --memory-plan and MXNET_LINT_MEMPLAN_BUDGET arm it
        self.memplan = memplan


# --------------------------------------------------------------- helpers
def _symbol_memo(symbol, slot, key, compute):
    """Per-symbol memo for the O(nodes) pass portions.

    Binds repeat over the same (symbol, shapes) — train/eval pairs,
    force_rebind, every step of a bucketing cycle — and the graph walks
    (fixpoint inference, name/attr scans) are the only non-trivial
    validation cost, so warm-bind validation runs at dict-lookup
    prices. The memo assumes the de-facto immutability of built graphs; mutating a
    node's attrs after a lint serves stale findings for that symbol
    object.
    """
    memo = getattr(symbol, "_mx_lint_memo", None)
    if memo is None:
        memo = {}
        try:
            symbol._mx_lint_memo = memo
        except AttributeError:
            return compute()
    cached = memo.get(slot)
    if cached is not None and cached[0] == key:
        return cached[1]
    value = compute()
    memo[slot] = (key, value)
    return value


def _entry_shapes_cached(symbol, known):
    """Fixpoint entry shapes, memoized per (symbol, seed shapes)."""
    key = tuple(sorted(known.items()))
    return _symbol_memo(symbol, "entry_shapes", key,
                        lambda: symbol._infer_entry_shapes(known))


def _known_shapes(ctx):
    """Seed shapes: explicit ctx shapes, else every bound arg array."""
    if ctx.known_shapes:
        return dict(ctx.known_shapes)
    exe = ctx.executor
    if exe is not None:
        return {nm: tuple(a.shape)
                for nm, a in zip(exe.arg_names, exe.arg_arrays)
                if a is not None}
    return {}


# ================================================================ passes
def graph_verifier(ctx, out):
    """GV1xx: the InferShape/InferType discipline plus graph structure.

    JSON-only structural rules (GV106 dangling input, GV108 dead node)
    live in the same pass but run off ``ctx.json_graph`` because a
    loaded Symbol cannot represent either state (load_json would have
    crashed, and _topo_nodes only walks reachable nodes).
    """
    if ctx.json_graph is not None:
        _verify_json_graph(ctx.json_graph, out)
    sym = ctx.symbol
    if sym is None:
        return
    known = _known_shapes(ctx)
    shapes_key = tuple(sorted(known.items()))
    out.extend(_symbol_memo(
        sym, "graph_verifier", shapes_key,
        lambda: _verify_symbol(sym, known)))

    # GV105: declared dtype vs bound dtype (the declared-var list is
    # shape-independent — memoize it; the dtype compare is per binding)
    exe = ctx.executor
    if exe is not None:
        declared_vars = _symbol_memo(
            sym, "declared_dtypes", None,
            lambda: [(n.name, str(n._extra["__dtype__"]))
                     for n in sym._topo_nodes()
                     if n.is_variable and "__dtype__" in n._extra])
        if declared_vars:
            bound = dict(zip(exe.arg_names, exe.arg_arrays))
            for name, declared in declared_vars:
                arr = bound.get(name)
                if arr is None:
                    continue
                if str(np.dtype(arr.dtype)) != str(np.dtype(declared)):
                    out.append(Diagnostic(
                        "GV105", f"variable {name!r} declares dtype "
                        f"{declared} but is bound to "
                        f"{np.dtype(arr.dtype)}", node=name,
                        hint="bind an array of the declared dtype or "
                             "drop the declaration"))


def _verify_symbol(sym, known):
    """The symbol-level GV rules (everything derivable from the graph +
    seed shapes alone); memoized per (symbol, shapes)."""
    out = []
    nodes = sym._topo_nodes()

    # GV103/GV104: name collisions. Binding, attr_dict and the JSON wire
    # format all key by name — two distinct nodes sharing one are
    # silently merged on reload or bound to one buffer.
    seen = {}
    for n in nodes:
        other = seen.get(n.name)
        if other is None:
            seen[n.name] = n
        elif other is not n:
            if n.is_variable or other.is_variable:
                out.append(Diagnostic(
                    "GV103", f"variable name {n.name!r} is used by two "
                    "distinct nodes; binding by name is ambiguous",
                    node=n.name,
                    hint="rename one of the variables"))
            else:
                out.append(Diagnostic(
                    "GV104", f"op node name {n.name!r} is used by two "
                    "distinct nodes; attrs and JSON round-trips will "
                    "merge them", node=n.name, op=n.op,
                    hint="pass unique name= to the symbol calls"))

    # GV101/GV102/GV107: run the same fixpoint inference bind runs,
    # seeded with everything known, and audit what it could not settle.
    try:
        entry = _entry_shapes_cached(sym, known)
    except MXNetError as e:
        out.append(Diagnostic(
            "GV101", str(e),
            hint="fix the conflicting shapes (the message carries the "
                 "failing node's op, name, and input shapes)"))
        return out
    except Exception as e:  # noqa: BLE001 — a broken infer fn is a finding
        out.append(Diagnostic(
            "GV101", f"shape inference crashed: {type(e).__name__}: {e}",
            hint="fix the op's infer_shape function"))
        return out

    stalled_ops = set()
    for n in nodes:
        store = entry.get(id(n))
        if store is None:
            continue
        unknown = [s is None or 0 in s for s in store]
        if n.is_variable:
            continue
        if all(unknown) and n.op not in stalled_ops:
            in_known = any(
                (entry.get(id(inp)) or [None])[idx] is not None
                for inp, idx in n.inputs if id(inp) in entry
                and idx < len(entry[id(inp)]))
            opdef = n.opdef()
            if (in_known and opdef.infer_shape is None
                    and not getattr(opdef, "shape_passthrough", False)):
                stalled_ops.add(n.op)
                out.append(Diagnostic(
                    "GV107", f"op {n.op!r} has no infer_shape and no "
                    "shape_passthrough flag; inference stalls on "
                    "partial input shapes", node=n.name, op=n.op,
                    hint="register infer_shape (or shape_passthrough="
                         "True for identity-shaped ops)"))

    if known:
        # with seeds present, whatever stayed unknown will stay unknown
        # at run time too — the bind will allocate nothing for it
        missing = []
        for n in nodes:
            if n.is_variable:
                s = entry[id(n)][0]
                if s is None or 0 in s:
                    missing.append(n.name)
        for node, idx in sym._outputs:
            store = entry.get(id(node))
            s = store[idx] if store and idx < len(store) else None
            if s is None or 0 in s:
                missing.append(f"output {node.name}[{idx}]")
                break
        if missing:
            out.append(Diagnostic(
                "GV102", "shape inference left "
                f"{', '.join(missing[:6])} unknown"
                + (f" (+{len(missing) - 6} more)"
                   if len(missing) > 6 else ""),
                hint="provide more input shapes or register the missing "
                     "infer_shape functions"))
    return out


def _verify_json_graph(graph, out):
    """GV106/GV108 over a raw symbol-JSON dict."""
    nodes = graph.get("nodes") or []
    heads = graph.get("heads") or []
    for i, jn in enumerate(nodes):
        for ref in jn.get("inputs") or []:
            src = ref[0] if ref else -1
            if not (0 <= src < i):
                out.append(Diagnostic(
                    "GV106", f"node {jn.get('name', i)!r} input refers "
                    f"to node {src}, which is "
                    + ("out of range" if not (0 <= src < len(nodes))
                       else "not topologically earlier"),
                    node=jn.get("name"), op=jn.get("op"),
                    hint="the graph JSON is corrupt; regenerate it"))
    reach = set()
    stack = [h[0] for h in heads if h and 0 <= h[0] < len(nodes)]
    while stack:
        i = stack.pop()
        if i in reach:
            continue
        reach.add(i)
        for ref in nodes[i].get("inputs") or []:
            if ref and 0 <= ref[0] < len(nodes):
                stack.append(ref[0])
    for i, jn in enumerate(nodes):
        if i not in reach:
            out.append(Diagnostic(
                "GV108", f"node {jn.get('name', i)!r} is unreachable "
                "from every head", node=jn.get("name"), op=jn.get("op"),
                hint="dead nodes bloat checkpoints and mask wiring "
                     "mistakes; drop them or re-head the graph"))


def donation_checker(ctx, out):
    """DA2xx: buffer ownership through the donated fused/scan plans.

    The fused/scan programs donate their watched params and optimizer
    states (executor_group.py donate_argnums=(0, 4)); XLA then reuses
    those buffers for the outputs and *deletes* the inputs. Any other
    holder of the same buffer — a second arg name, an optimizer-state
    leaf, a shared group's cell — reads a deleted array on its next
    access. PR 2 poisons grads at runtime; these rules find the alias
    before the first step runs.
    """
    g = ctx.exec_group
    if g is None or getattr(g, "_fused_prog", None) is None:
        _bucket_alias_check(ctx, out)
        return
    exe = g.executor
    watched = list(getattr(g, "_fused_watched", ()) or ())
    wset = set(watched)

    # DA203: a donated param name that is also a data/label input would
    # ride in both the donated dict and the aliased `rest` dict
    for nm in watched:
        if nm in set(g.data_names) | set(g.label_names):
            out.append(Diagnostic(
                "DA203", f"parameter {nm!r} is donated by the fused "
                "step but is also a data/label input of the binding",
                node=nm,
                hint="exclude it from the trained params (fixed_param_"
                     "names) or rename the input"))

    # DA201: identity aliasing. Two views: NDArray cells bound under
    # two names, and one jax buffer behind two cells/state leaves.
    entries = []      # (name, kind, cell, buffer)
    for nm, arr in zip(exe.arg_names, exe.arg_arrays):
        if arr is not None:
            entries.append((nm, "arg", arr, arr.asjax()))
    for nm, arr in zip(exe.arg_names, exe.grad_arrays):
        if arr is not None:
            entries.append((nm, "grad", arr, arr.asjax()))
    for nm, arr in zip(exe.aux_names, exe.aux_arrays):
        if arr is not None:
            entries.append((nm, "aux", arr, arr.asjax()))
    import jax as _jax
    for nm in watched:
        st = getattr(g, "_fused_states", {}).get(nm)
        if st is not None:
            for leaf in _jax.tree.leaves(st):
                entries.append((nm, "state", None, leaf))

    donated = {(nm, kind) for nm, kind, _cell, _buf in entries
               if kind in ("arg", "state") and nm in wset}
    by_cell, by_buf = {}, {}
    for nm, kind, cell, buf in entries:
        if cell is not None:
            by_cell.setdefault(id(cell), []).append((nm, kind))
        if buf is not None:
            by_buf.setdefault(id(buf), []).append((nm, kind))
    flagged = set()
    for holders in list(by_cell.values()) + list(by_buf.values()):
        if len(holders) < 2:
            continue
        donated_holders = [h for h in holders if h in donated]
        if not donated_holders:
            continue
        key = tuple(sorted(set(holders)))
        if key in flagged:
            continue
        flagged.add(key)
        desc = ", ".join(f"{nm} ({kind})" for nm, kind in key)
        out.append(Diagnostic(
            "DA201", "one buffer is bound under multiple entries — "
            f"{desc} — and the fused step donates it; the other "
            "holder(s) would read a deleted array", node=key[0][0],
            hint="copy the array before binding (jnp.array(x, "
                 "copy=True)) or drop the extra binding"))

    # DA202: donation into cells shared with another group (bucketing /
    # shared_module): the sharer's pending programs may still hold the
    # pre-donation buffer
    if wset & set(getattr(g, "_shared_param_names", ()) or ()):
        shared = sorted(wset & set(g._shared_param_names))
        out.append(Diagnostic(
            "DA202", "fused step donates parameter cells shared with "
            f"another executor group: {', '.join(shared[:4])}"
            + (f" (+{len(shared) - 4} more)" if len(shared) > 4 else ""),
            node=shared[0],
            hint="borrow_optimizer/staged updates for shared groups, or "
                 "rebind without shared_module"))

    _bucket_alias_check(ctx, out)


def _bucket_alias_check(ctx, out):
    """DA204: one buffer staged under two keys in one flush window —
    both keys' segments of the flat bucket would scatter back into the
    same destination."""
    sched = ctx.sched
    if sched is None:
        return
    windows = {}
    for rec in getattr(sched, "stage_log", ()):
        windows.setdefault(rec.get("window"), []).append(rec)
    for recs in windows.values():
        by_buf = {}
        for r in recs:
            if r.get("buf") is not None:
                by_buf.setdefault(r["buf"], set()).add(r["key"])
        for keys in by_buf.values():
            if len(keys) > 1:
                out.append(Diagnostic(
                    "DA204", "one gradient buffer was staged under "
                    f"kvstore keys {sorted(keys)} in the same bucket "
                    "window",
                    hint="push distinct arrays per key (the reduced "
                         "segments write back to one destination)"))
                return


def collective_order(ctx, out):
    """CO3xx: every worker must dispatch the same collective sequence.

    A collective is a rendezvous: if worker A dispatches bucket(k3,k4)
    while worker B — whose backward happened to finish k4 first —
    dispatches bucket(k4,k3), the mesh deadlocks. The order must
    therefore be a *total* order derived from data every worker shares
    (key ids, declared priorities), never from grad-ready arrival time.
    """
    # CO301: audit the staged push plan recorded by the scheduler
    sched = ctx.sched
    multi = ctx.assume_multiworker
    kv = ctx.kvstore
    if kv is not None and getattr(kv, "_nproc", 1) > 1:
        multi = True
    if sched is not None and multi:
        windows = {}
        for rec in getattr(sched, "stage_log", ()):
            windows.setdefault(rec.get("window"), []).append(rec)
        for recs in windows.values():
            by_prio = {}
            for r in recs:
                by_prio.setdefault(r.get("prio", 0), set()).add(
                    r.get("push", 0))
            bad = {p: pushes for p, pushes in by_prio.items()
                   if len(pushes) > 1}
            if bad:
                prio = sorted(bad)[0]
                out.append(Diagnostic(
                    "CO301", f"{sum(len(v) for v in bad.values())} push "
                    f"calls staged gradients at equal priority "
                    f"(e.g. {prio}) in one bucket window; bucket "
                    "composition then follows per-worker grad-ready "
                    "order and the collectives diverge across workers",
                    hint="push all keys in ONE call, or give every key "
                         "a distinct priority (Module.update does both)"))
                break

    g = ctx.exec_group
    mod = ctx.module
    # CO302: two reduction plans over the same gradients
    if g is not None and getattr(g, "_zero_plan", None) is not None:
        kv = kv or (getattr(mod, "_kvstore", None) if mod else None)
        if kv is not None and "dist" in getattr(kv, "type", ""):
            plan = g._zero_plan.describe()
            out.append(Diagnostic(
                "CO302", f"ZeRO in-program plan {plan} is armed while "
                f"a {kv.type!r} kvstore also reduces gradients; the "
                "gradients would be summed twice in an undefined order",
                hint="use zero_stage only with the in-program plan "
                     "(kvstore=None/local) or disable zero_stage"))

    # CO303: the fused/scan program's collective sequence is the watched
    # list; it must match declaration order, the one order every worker
    # derives identically from the symbol
    if g is not None and getattr(g, "_fused_prog", None) is not None:
        watched = list(getattr(g, "_fused_watched", ()) or ())
        expect = [nm for nm in g.param_names
                  if g.grad_req.get(nm) == "write"]
        if watched != expect:
            out.append(Diagnostic(
                "CO303", "fused-step collective order "
                f"{watched[:4]}... diverges from parameter declaration "
                f"order {expect[:4]}...",
                hint="do not reorder _fused_watched; both lists must "
                     "derive from symbol.list_arguments()"))


def sharding_checker(ctx, out):
    """SH6xx: the SPMD plan vs what is actually bound.

    Only active on bindings carrying a ``parallel/spmd.SpmdPlan``: the
    plan's PartitionSpecs are the contract every placement and the
    fused program's donation discipline depend on — an array re-bound
    with the wrong sharding silently changes the program XLA partitions
    (wrong collective structure, broken donation aliasing), which no
    runtime check catches before the step count makes it expensive.
    """
    g = ctx.exec_group
    plan = getattr(g, "_spmd_plan", None) if g is not None else None
    if plan is None:
        return
    exe = g.executor
    from jax.sharding import NamedSharding

    def matches(arr, want):
        """Does a bound jax array's sharding realize ``want``?"""
        try:
            sh = arr.sharding
            if hasattr(sh, "is_equivalent_to"):
                return sh.is_equivalent_to(want, arr.ndim)
            return str(sh) == str(want)
        except Exception:
            return True            # unknown sharding kinds: no finding

    # SH601: bound param/aux arrays vs the plan's specs (data/label
    # arrays are re-placed per batch and are not audited here)
    ad = exe.arg_dict
    for nm in g.param_names:
        arr = ad.get(nm)
        if arr is None:
            continue
        want = plan.param_sharding(nm)
        if not matches(arr.asjax(), want):
            out.append(Diagnostic(
                "SH601", f"parameter {nm!r} is bound with sharding "
                f"{arr.asjax().sharding} but the SPMD plan places it as "
                f"{want.spec}", node=nm,
                hint="place params through the plan (set_params / "
                     "exec_group._place); do not _set raw device arrays"))

    # SH602: a ctx_group-tagged param the plan could NOT shard over the
    # model axis — it silently replicates, paying full memory on every
    # device of the axis the tag asked to split over
    for nm, reason in sorted(plan.unsharded_tagged.items()):
        out.append(Diagnostic(
            "SH602", f"parameter {nm!r} is ctx_group-tagged for the "
            f"model axis but stays fully replicated: {reason}", node=nm,
            hint="pad the dimension to a multiple of the axis size, "
                 "shrink the model axis, or drop the ctx_group tag"))

    # SH603: donation over the spmd carry — the fused program donates
    # watched params and state leaves and emits outputs constrained to
    # the plan's specs; an input whose committed sharding differs can't
    # alias its output buffer (XLA copies: double memory, or deletes a
    # still-referenced buffer under a later reshard)
    if getattr(g, "_fused_prog", None) is not None:
        watched = list(getattr(g, "_fused_watched", ()) or ())
        states = getattr(g, "_fused_states", {}) or {}
        import jax as _jax
        for nm in watched:
            arr = ad.get(nm)
            if arr is not None and not matches(arr.asjax(),
                                               plan.param_sharding(nm)):
                out.append(Diagnostic(
                    "SH603", f"donated parameter {nm!r} enters the "
                    "fused step with a sharding that differs from the "
                    "program's output spec "
                    f"{plan.param_spec(nm)}; donation cannot alias",
                    node=nm,
                    hint="re-place the param per the plan before the "
                         "next step (set_params does this)"))
                continue
            want_state = plan.state_sharding(nm)
            for leaf in _jax.tree.leaves(states.get(nm, ())):
                shaped_like_param = getattr(leaf, "shape", None) == \
                    getattr(arr, "shape", None)
                want = want_state if (plan.zero or shaped_like_param) \
                    else plan.replicated
                if not matches(leaf, want):
                    out.append(Diagnostic(
                        "SH603", f"optimizer-state leaf of {nm!r} is "
                        f"sharded {leaf.sharding} but the plan's state "
                        f"spec is {want.spec}; the donated carry "
                        "cannot alias", node=nm,
                        hint="import states through "
                             "import_fused_states/import_staged_state"))
                    break


def retrace_churn(ctx, out):
    """RC4xx: what would mint a new program_cache key per step.

    The process-wide program cache keys on (symbol sha1, shapes/dtypes,
    ...). Anything unstable inside that key — an attr whose repr embeds
    an object id, an array attr whose repr truncates (two DIFFERENT
    graphs hash equal: worse), a NaN that never compares equal in the
    lr/wd value cache — turns the cache into a per-step recompile.
    """
    sym = ctx.symbol
    if sym is not None:
        out.extend(_symbol_memo(sym, "unstable_attrs", None,
                                lambda: _unstable_attr_findings(sym)))

    exe = ctx.executor
    if exe is not None and getattr(exe, "_prog_cache_base", None) is None \
            and getattr(exe, "_mp_plan", None) is None:
        out.append(Diagnostic(
            "RC402", "this binding has no program-cache key; every "
            "rebind (train/eval pair, force_rebind, bucketing) "
            "re-traces and recompiles",
            hint="make the symbol JSON-serializable (see the RC401 "
                 "findings, if any) so its signature hashes"))


def _unstable_attr_findings(sym):
    """RC401 scan over every node's attrs; memoized per symbol."""
    out = []
    flagged = set()
    for n in sym._topo_nodes():
        for k, v in list(n.attrs.items()) + list(n._extra.items()):
            ok, why = attr_cache_stable(v)
            if ok or (n.name, k) in flagged:
                continue
            flagged.add((n.name, k))
            out.append(Diagnostic(
                "RC401", f"attr {k!r} = {type(v).__name__} on node "
                f"{n.name!r} is not cache-key stable ({why})",
                node=n.name, op=n.op,
                hint="use plain str/int/float/bool/tuple attr "
                     "values; pass arrays as graph inputs, not "
                     "attrs"))
    return out


def host_sync(ctx, out):
    """HS5xx: implicit device->host transfers in the fit hot path."""
    env = os.environ
    exe = ctx.executor
    if env.get("MXNET_ENGINE_TYPE") == "NaiveEngine":
        out.append(Diagnostic(
            "HS501", "MXNET_ENGINE_TYPE=NaiveEngine forces every op to "
            "complete on the host before the next dispatches",
            hint="debug mode only; unset it for training runs"))
    if exe is not None and getattr(exe, "_monitor_callback", None) \
            is not None:
        out.append(Diagnostic(
            "HS502", "a monitor callback is installed: every batch "
            "replays eagerly with per-op device->host transfers",
            hint="remove the monitor for production runs"))
    sym = ctx.symbol
    training = False
    if ctx.exec_group is not None:
        training = bool(getattr(ctx.exec_group, "for_training", False))
    elif exe is not None:
        training = any(r != "null"
                       for r in getattr(exe, "grad_req", {}).values())
    if sym is not None and training:
        for node, idx in sym._outputs:
            if node.is_variable:
                out.append(Diagnostic(
                    "HS503", f"training output {node.name!r} is a bare "
                    "input variable; it is re-materialized (and "
                    "typically host-read) every step", node=node.name,
                    hint="drop the passthrough head or wrap it in "
                         "BlockGrad outside the train symbol"))
                break
    if ctx.exec_group is not None \
            and getattr(ctx.exec_group, "_fused_prog", None) is not None \
            and env.get("MXNET_FUSED_KEEP_GRADS", "0") == "1":
        out.append(Diagnostic(
            "HS504", "MXNET_FUSED_KEEP_GRADS=1 emits every gradient as "
            "a fused-program output (~5% step time) and keeps it "
            "host-readable",
            hint="unset it unless something reads grad_dict mid-run"))


def mfu_coverage(ctx, out):
    """MF601: ops with nodes in this graph but no cost metadata.

    The MFU/roofline accounting (telemetry/mfu.py) folds per-op
    ``flops``/``bytes_moved`` estimators over the graph; an op without
    them silently under-counts every step it runs. One info finding per
    distinct op keeps the coverage gap visible (registry-wide audit:
    ``tools/mxlint.py --mfu-audit``).
    """
    sym = ctx.symbol
    if sym is None and ctx.executor is not None:
        sym = ctx.executor._symbol
    if sym is None:
        return

    def compute():
        missing = {}
        for node in sym._topo_nodes():
            if node.is_variable:
                continue
            if not node.opdef().has_cost():
                missing.setdefault(node.op, (node.name, 0))
                nm, n = missing[node.op]
                missing[node.op] = (nm, n + 1)
        return missing

    missing = _symbol_memo(sym, "mfu_coverage", True, compute)
    for op, (first_node, n) in sorted(missing.items()):
        out.append(Diagnostic(
            "MF601", f"op {op!r} ({n} node(s)) carries no flops/bytes "
            "cost metadata; MFU and roofline reports under-count it",
            node=first_node, op=op,
            hint="seed an estimator in ops/cost.py (or "
                 "OpDef.set_cost); list all gaps with "
                 "tools/mxlint.py --mfu-audit"))


def memory_planner(ctx, out):
    """ME8xx: the static memory planner as a lint pass.

    Inert unless armed — planning walks the graph per policy, which the
    warm-bind <2% overhead gate cannot absorb on every bind. Armed by an
    explicit ``AnalysisContext(memplan={...})`` (mxlint --memory-plan)
    or by ``MXNET_LINT_MEMPLAN_BUDGET`` (bytes, or "16G") for bindings
    that know their shapes. Options: ``capacity_bytes`` (default: the
    env budget, else ``telemetry.mfu.device_hbm_bytes()``), ``policy``
    (default: the active remat policy), ``buckets`` (ME802 ladder),
    plus anything ``memplan.plan_symbol`` takes.
    """
    opts = ctx.memplan
    if opts is None:
        raw = os.environ.get("MXNET_LINT_MEMPLAN_BUDGET", "").strip()
        if not raw:
            return
        mult = 1
        if raw[-1:].upper() == "G":
            raw, mult = raw[:-1], 1 << 30
        elif raw[-1:].upper() == "M":
            raw, mult = raw[:-1], 1 << 20
        try:
            opts = {"capacity_bytes": int(float(raw) * mult)}
        except ValueError:
            return
    opts = dict(opts)
    sym = ctx.symbol
    if sym is None and ctx.executor is not None:
        sym = ctx.executor._symbol
    if sym is None:
        return
    shapes = _known_shapes(ctx)
    g = ctx.exec_group
    if g is not None:
        shapes = {d.name: tuple(d.shape) for d in g.data_shapes}
        for l in (g.label_shapes or []):
            shapes[l.name] = tuple(l.shape)
    if not shapes:
        return
    from . import memplan as _memplan
    from ..telemetry.mfu import device_hbm_bytes
    capacity = opts.pop("capacity_bytes", None)
    if capacity is None:
        capacity = device_hbm_bytes()
    buckets = opts.pop("buckets", None)
    if "policy" not in opts:
        from .. import remat as _remat
        opts["policy"] = getattr(g, "_remat_policy", None) \
            if g is not None else None
        opts["policy"] = opts["policy"] or _remat.active()
    if g is not None:
        opts.setdefault("n_data", getattr(g, "_n_data", 1))
        opts.setdefault("for_training", bool(g.for_training))
        opts.setdefault("compute_dtype", g.compute_dtype)
    plan = _memplan.plan_symbol(sym, shapes, **opts)
    _memplan.record_plan(plan)
    out.extend(_memplan.plan_findings(plan, capacity_bytes=capacity,
                                      buckets=buckets))


from .precision import precision_flow  # noqa: E402  (pass body)

#: pass name -> callable(ctx, out_list); order is the report order
PASSES = OrderedDict([
    ("graph_verifier", graph_verifier),
    ("donation_checker", donation_checker),
    ("collective_order", collective_order),
    ("sharding_checker", sharding_checker),
    ("retrace_churn", retrace_churn),
    ("host_sync", host_sync),
    ("mfu_coverage", mfu_coverage),
    ("precision_flow", precision_flow),
    ("memory_planner", memory_planner),
])


# ========================================================== orchestration
def _disabled():
    raw = os.environ.get("MXNET_LINT_DISABLE", "")
    return {tok.strip() for tok in raw.split(",") if tok.strip()}


def run_passes(ctx, passes=None, mirror=True):
    """Run the (enabled) passes over ``ctx`` and return a Report.

    A pass that raises contributes an XX001 info finding instead of
    propagating — analysis must never break a bind.
    """
    disabled = _disabled()
    report = Report()
    if "all" in disabled:
        return report
    names = list(passes or PASSES)
    for name in names:
        if name in disabled:
            continue
        fn = PASSES[name]
        found = []
        try:
            fn(ctx, found)
        except Exception as e:  # noqa: BLE001 — observers must not throw
            log.debug("analysis pass %s failed", name, exc_info=True)
            found = [Diagnostic(
                "XX001", f"analysis pass {name!r} failed: "
                f"{type(e).__name__}: {e}",
                hint="report this; the pass was skipped")]
        for d in found:
            if d.rule not in disabled:
                report.add(d)
    if mirror and len(report):
        _mirror(report)
    return report


def _mirror(report):
    """Findings -> telemetry registry counters + flight-recorder ring."""
    try:
        from .. import telemetry as _telemetry
        for d in report:
            _telemetry.metrics.counter("analysis.lint.findings",
                                       rule=d.rule,
                                       severity=d.severity).inc()
            if _telemetry.enabled():
                # event() lands in the jsonl/chrome exporters AND the
                # flight ring; the direct note keeps the always-on ring
                # populated when the tracer is off
                _telemetry.event("lint.finding", rule=d.rule,
                                 severity=d.severity, node=d.node or "",
                                 message=d.message)
            else:
                _telemetry.flightrec.note("lint.finding", rule=d.rule,
                                          severity=d.severity,
                                          node=d.node or "",
                                          message=d.message)
    except Exception:  # noqa: BLE001 — telemetry must not break analysis
        log.debug("lint telemetry mirroring failed", exc_info=True)


# ---------------------------------------------------------- entry points
def lint_symbol(symbol, shapes=None, **ctx_kwargs):
    """Lint a free-standing Symbol; ``shapes`` seeds inference."""
    return run_passes(AnalysisContext(symbol=symbol, known_shapes=shapes,
                                      **ctx_kwargs))


def lint_executor(executor):
    """Lint one bound Executor (graph + binding-level rules)."""
    return run_passes(AnalysisContext(symbol=executor._symbol,
                                      executor=executor))


def lint_module(module):
    """Lint a bound Module: graph, binding, fused/ZeRO/scan plans, and
    the kvstore comm plan when one is attached."""
    g = module._exec_group
    kv = getattr(module, "_kvstore", None)
    return run_passes(AnalysisContext(
        symbol=module._symbol,
        executor=g.executor if g is not None else None,
        exec_group=g, module=module, kvstore=kv,
        sched=getattr(kv, "_sched", None)))


def lint_json(text_or_dict, shapes=None):
    """Lint a symbol JSON (file contents or parsed dict): structural
    rules over the raw graph plus the full pass set over the loaded
    Symbol when it loads."""
    graph = text_or_dict
    if isinstance(graph, (str, bytes)):
        graph = _json.loads(graph)
    symbol = None
    load_error = None
    try:
        from .. import symbol as _symbol_mod
        symbol = _symbol_mod.load_json(_json.dumps(graph))
    except Exception as e:  # noqa: BLE001 — corrupt JSON is the finding
        load_error = e
    report = run_passes(AnalysisContext(symbol=symbol, known_shapes=shapes,
                                        json_graph=graph))
    if load_error is not None and "GV106" not in report.rules:
        report.add(Diagnostic(
            "GV106", f"symbol JSON does not load: "
            f"{type(load_error).__name__}: {load_error}",
            hint="regenerate the JSON with Symbol.save()"))
    return report


# ------------------------------------------------------- bind-time hooks
def resolve_mode(explicit=None):
    """'warn' | 'raise' | None from an explicit arg or the env knob."""
    mode = explicit
    if mode is None:
        mode = os.environ.get("MXNET_GRAPH_VALIDATE", "")
    if isinstance(mode, str):
        mode = mode.strip().lower()
    if mode in ("warn", "raise"):
        return mode
    if mode in (None, "", "0", "off", "false", "none"):
        return None
    log.warning("unknown MXNET_GRAPH_VALIDATE mode %r; using 'warn'", mode)
    return "warn"


def _apply_mode(report, mode, where):
    if not len(report):
        return report
    logged = report.warnings
    if mode == "warn":
        logged = logged + report.errors
    for d in logged:
        log.warning("[%s] %s", where, d.format())
    if mode == "raise" and report.errors:
        raise MXNetError(
            f"graph validation failed at {where} with "
            f"{len(report.errors)} error(s):\n"
            + "\n".join(d.format() for d in report.errors))
    return report


def validate_executor(executor, mode):
    """bind-time hook: lint the freshly bound executor per ``mode``."""
    mode = resolve_mode(mode)
    if mode is None:
        return None
    return _apply_mode(lint_executor(executor), mode, "bind")


def validate_module(module, mode=None):
    """init_optimizer-time hook: lint the armed module per ``mode``."""
    mode = resolve_mode(mode)
    if mode is None:
        return None
    return _apply_mode(lint_module(module), mode, "init_optimizer")
