"""Executor: binds a Symbol to a device and runs it.

Reference pipeline (reference: src/executor/graph_executor.cc:333-446):
``Bind`` runs Gradient/PlaceDevice/InferShape/PlanMemory passes, allocates a
memory pool, wraps nodes in cached engine ops, and ``Forward``/``Backward``
push them to the dependency engine.

TPU-native pipeline: ``bind`` topologically closes the Symbol into ONE pure
JAX function and hands it to ``jax.jit`` — XLA performs memory planning,
fusion, scheduling and (on request) ``jax.vjp`` performs the Gradient pass.
Three compiled programs are built lazily per executor:

  * ``fwd_infer``  — forward, is_train=False (prediction path);
  * ``fwd_train``  — forward, is_train=True (dropout on, BN batch stats);
  * ``fwd_bwd``    — forward + cotangent propagation in a single XLA
    program — the analog of the reference's bulk-exec segment covering the
    whole fwd+bwd graph (graph_executor.cc:678-756), and the hot path of
    ``Module.fit``.

Laziness contract: ``forward(is_train=True)`` only *records* inputs; the
computation happens on first access of ``outputs`` (fwd program) or at
``backward()`` (fused program) — so a ``forward_backward`` pair costs exactly
one XLA execution, like the reference's single engine pass, while
``forward``-then-read still behaves eagerly from the caller's view.

Mutation contract: ``backward()`` applies ``grad_req`` (write/add) by
swapping new buffers into the bound grad NDArrays; aux states (BN moving
stats) are swapped after every training forward — Python aliases stay
coherent because NDArray is a mutable cell (see ndarray.py).
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from .base import MXNetError
from .context import current_context
from .ndarray import NDArray, zeros as nd_zeros
from .ops.registry import get_op
from . import kernel_tier as _kernel_tier
from . import program_cache as _progcache
from . import random as _random
from . import telemetry as _telemetry
from .telemetry import optable as _optable

__all__ = ["Executor", "naive_engine_active"]


def naive_engine_active():
    """True when ``MXNET_ENGINE_TYPE=NaiveEngine`` — the one-switch
    deterministic debug mode (reference: env_var.md:33-40, engine
    selection src/engine/engine.cc:13-40). Executor programs then run
    un-jitted, op by op, each op forced to completion before the next —
    serial replay for debugging, exactly what the reference's error
    message recommends (threaded_engine.h:330-338). Read at use time so
    tests (and users mid-session) can flip it."""
    import os
    return os.environ.get("MXNET_ENGINE_TYPE") == "NaiveEngine"


class _LazyOutputs:
    """List-like view of an executor's outputs that defers execution.

    ``forward(is_train=True)`` must not force the forward program: the
    hot path is ``backward()``'s single fused fwd+bwd XLA execution, and
    materializing here would run the forward twice per training step.
    Any actual access (len/index/iter) materializes via the ``outputs``
    property.
    """

    __slots__ = ("_exe",)

    def __init__(self, exe):
        self._exe = exe

    def _mat(self):
        return self._exe.outputs

    def __len__(self):
        return len(self._mat())

    def __getitem__(self, i):
        return self._mat()[i]

    def __iter__(self):
        return iter(self._mat())

    def __repr__(self):
        return repr(self._mat())


def loss_label_names(symbol):
    """The variables fed straight into a loss head's label slot: they
    keep their dtype under mixed precision (class ids must stay exact).
    Every other floating variable is what ``_load_var`` casts to the
    compute dtype in the step program - the set a serving binding
    narrows once, at bind (executor_group.serving_width_params)."""
    names = set()
    for node in symbol._topo_nodes():
        if not node.is_variable and node.opdef().is_loss:
            for inp, _ in node.inputs[1:]:
                if inp.is_variable:
                    names.add(inp.name)
    return names


def _build_graph_runner(symbol, shape_overrides=None, tap=None, mp_plan=None,
                        compute_dtype=None, remat_segments=0,
                        spmd_plan=None, n_devices=1):
    """Close the symbol graph into run(arg_vals, aux_vals, is_train, rng).

    Returns (runner, arg_names, aux_names, loss_mask). The runner is pure:
    dict-of-arrays in, (outputs, new_aux_dict) out — directly jittable.

    ``shape_overrides`` maps id(node) -> concrete shape for init-style ops
    whose declared shape had unknown (0) dims — e.g. RNN begin_state
    ``sym.zeros(shape=(0, H))`` resolved to the bound batch size (the
    reference resolves these in PlanMemory; here at runner-build time).

    ``tap(node, outputs)`` — optional per-op observation hook called after
    every non-variable node (the analog of the reference's per-op monitor
    callback, graph_executor.cc:758-778). Only meaningful when the runner
    executes un-jitted (eager per-op dispatch).

    ``mp_plan`` — optional ModelParallelPlan (parallel/placement.py): its
    boundary constraints are applied to cross-ctx_group edges, lowering
    the reference's PlaceDevice/_CrossDeviceCopy onto sharding
    constraints that XLA turns into collectives.

    ``compute_dtype`` — mixed precision: float variables are cast to this
    dtype (normally bfloat16 -> MXU-native matmuls/convs) at graph entry
    while the bound arrays (master params) stay float32; the cast's vjp
    upcasts gradients back automatically. Labels feeding a loss head are
    exempt (class indices above 256 don't survive a bfloat16 roundtrip).

    ``remat_segments`` — gradient mirroring (reference:
    MXNET_BACKWARD_DO_MIRROR, graph_executor.cc:210-223): when > 1, the
    compute nodes are split into that many contiguous segments and each is
    wrapped in ``jax.checkpoint``, so backward stores only segment-boundary
    activations and recomputes the interior — sqrt(N)-checkpointing bounds
    activation memory for deep unrolled graphs.

    Every op executes under ``jax.named_scope(node.name)``, so compiled
    HLO instructions carry Symbol node names into xplane/profiler traces —
    the analog of the reference's PROFILER_MESSAGE per-op naming
    (threaded_engine.h:296-307). ``telemetry/optable.py`` reads them back
    out of the compiled text: ``mx.profiler.operator_table`` is a
    program's device time by these names.
    """
    nodes = symbol._topo_nodes()
    node_index = {id(n): i for i, n in enumerate(nodes)}
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    shape_overrides = shape_overrides or {}

    # NHWC layout pass (ops/layout.py): on for the compiled hot path;
    # debug runners (monitor tap, NaiveEngine) and model-parallel plans
    # stay reference-layout so per-op observations match the reference
    from .ops import layout as _layout
    layout_opt = (tap is None and mp_plan is None
                  and _layout.layout_opt_enabled())
    entry_tags = {}     # (node_idx, out_idx) -> True when value is NHWC
    loss_mask = []
    for node, _ in symbol._outputs:
        loss_mask.append(bool(not node.is_variable and
                              node.opdef().is_loss))

    label_names = set()
    if compute_dtype is not None:
        compute_dtype = np.dtype(compute_dtype)
        # labels, and the state an op declares float32 (a recurrence's:
        # ``OpDef.aux_dtypes``), stay at their own width
        label_names = loss_label_names(symbol) | {
            n.name for n in nodes if n.is_variable
            and n._extra.get("__is_aux__")
            and n._extra.get("__dtype__") == "float32"}

    def _load_var(val, name):
        if (compute_dtype is not None and name not in label_names
                and jnp.issubdtype(val.dtype, jnp.floating)):
            return val.astype(compute_dtype)
        return val

    def _exec_node(i, get_in, arg_vals, aux_vals, is_train, rng, new_aux):
        """Run compute node i; inputs via get_in((producer_idx, out_idx))."""
        node = nodes[i]
        opdef = node.opdef()
        attrs = node.attrs
        if id(node) in shape_overrides:
            attrs = {**attrs, "shape": shape_overrides[id(node)]}
        aux_n = len(opdef.aux_names(attrs))
        in_entries, in_tags = [], []
        for inp, idx in node.inputs:
            if inp.is_variable:
                if inp._extra.get("__is_aux__"):
                    in_entries.append(_load_var(aux_vals[inp.name],
                                                inp.name))
                else:
                    in_entries.append(_load_var(arg_vals[inp.name],
                                                inp.name))
                in_tags.append(False)
            else:
                key = (node_index[id(inp)], idx)
                in_entries.append(get_in(key))
                in_tags.append(entry_tags.get(key, False))
        regular = in_entries[:len(in_entries) - aux_n] if aux_n \
            else in_entries
        aux = in_entries[len(in_entries) - aux_n:] if aux_n else []
        krng = jax.random.fold_in(rng, i) if opdef.need_rng else None
        # per-op attribution: dispatch counts per registered op plus a
        # span per node execution. Under jax.jit this fires at trace time
        # (once per compile — the spans nest under executor.compile);
        # under the NaiveEngine/tapped runners it fires per step with
        # real per-op wall time, the reference's per-op profile records.
        if _telemetry.enabled():
            _telemetry.counter("executor.op_dispatch", op=node.op).inc()
            op_span = _telemetry.span("op." + node.op, node=node.name)
        else:
            op_span = _telemetry.null_span
        with op_span, jax.named_scope(node.name):
            out_tags = None
            if layout_opt:
                res = _layout.nhwc_exec(opdef, attrs, regular, aux,
                                        in_tags[:len(regular)],
                                        is_train, krng)
                if res is not None:
                    outs, aux_out, out_tags = res
            if out_tags is None:
                regular = [_layout.to_nchw(x) if t else x
                           for x, t in zip(regular, in_tags)]
                outs, aux_out = _kernel_tier.dispatch(
                    opdef, attrs, regular, aux, is_train, krng,
                    spmd_plan=spmd_plan, n_devices=n_devices)
                out_tags = [False] * len(outs)
        for j, t in enumerate(out_tags):
            entry_tags[(i, j)] = t
        if mp_plan is not None:
            outs = mp_plan.constrain(id(node), outs)
        if tap is not None:
            tap(node, outs)
        # training aux (BatchNorm moving stats) updates only under
        # is_train; a stateful_infer op (KV-cache decode) reads AND
        # writes its aux on inference forwards too — the cache advance
        # IS the inference step's side effect
        if aux_n and (is_train or opdef.stateful_infer):
            for (inp, _), new_val in zip(
                    node.inputs[len(node.inputs) - aux_n:], aux_out):
                new_aux[inp.name] = new_val
        return outs

    out_entries = []
    for n, i in symbol._outputs:
        if n.is_variable:
            out_entries.append(("var", n.name,
                                bool(n._extra.get("__is_aux__"))))
        else:
            out_entries.append(("node", node_index[id(n)], i))

    def _emit_outputs(get_entry, arg_vals, aux_vals):
        outs = []
        for ent in out_entries:
            if ent[0] == "var":
                src = aux_vals if ent[2] else arg_vals
                outs.append(_load_var(src[ent[1]], ent[1]))
            else:
                o = get_entry((ent[1], ent[2]))
                # user-visible outputs are always reference-layout NCHW
                if entry_tags.get((ent[1], ent[2]), False):
                    o = _layout.to_nchw(o)
                outs.append(o)
        return outs

    compute_idx = [i for i, n in enumerate(nodes) if not n.is_variable]

    def run(arg_vals, aux_vals, is_train, rng):
        vals = {}       # (node_idx, out_idx) -> array
        new_aux = {}
        for i in compute_idx:
            outs = _exec_node(i, vals.__getitem__, arg_vals, aux_vals,
                              is_train, rng, new_aux)
            for j, o in enumerate(outs):
                vals[(i, j)] = o
        outputs = _emit_outputs(vals.__getitem__, arg_vals, aux_vals)
        return outputs, new_aux

    if remat_segments and remat_segments > 1 and len(compute_idx) > 2:
        run = _segmented_runner(
            nodes, node_index, compute_idx, out_entries, _exec_node,
            _emit_outputs, min(int(remat_segments), len(compute_idx)))

    return run, arg_names, aux_names, loss_mask


def _segmented_runner(nodes, node_index, compute_idx, out_entries,
                      exec_node, emit_outputs, n_seg):
    """sqrt(N)-style remat: contiguous node segments under jax.checkpoint.

    Only segment-boundary entries (values consumed by a later segment or
    emitted as outputs) thread through the carry; everything interior to a
    segment is recomputed during backward instead of stored. The carry is
    a dict keyed "i:j" (producer node index : output index) so it stays a
    plain jittable pytree.
    """
    seg_size = -(-len(compute_idx) // n_seg)
    segments = [compute_idx[k:k + seg_size]
                for k in range(0, len(compute_idx), seg_size)]
    seg_of = {}
    for s, seg in enumerate(segments):
        for i in seg:
            seg_of[i] = s

    # liveness: last segment that still reads each escaping entry
    # (outputs live to the very end); dead entries drop out of the carry
    # at each boundary so the stored set stays minimal
    last_use = {}
    for i in compute_idx:
        for inp, idx in nodes[i].inputs:
            if not inp.is_variable:
                p = node_index[id(inp)]
                if seg_of[p] != seg_of[i]:
                    key = (p, idx)
                    last_use[key] = max(last_use.get(key, -1), seg_of[i])
    for ent in out_entries:
        if ent[0] == "node":
            last_use[(ent[1], ent[2])] = len(segments)

    def run(arg_vals, aux_vals, is_train, rng):
        def make_seg(s, seg_nodes):
            def seg_fn(carry, rng_in):
                local = {}
                new_aux_loc = {}

                def get_in(key):
                    if key in local:
                        return local[key]
                    return carry[f"{key[0]}:{key[1]}"]

                for i in seg_nodes:
                    outs = exec_node(i, get_in, arg_vals, aux_vals,
                                     is_train, rng_in, new_aux_loc)
                    for j, o in enumerate(outs):
                        local[(i, j)] = o
                out = {}
                for k, v in carry.items():
                    if k.startswith("aux:") or \
                            last_use[tuple(map(int, k.split(":")))] > s:
                        out[k] = v
                for key, lu in last_use.items():
                    if key in local and lu > s:
                        out[f"{key[0]}:{key[1]}"] = local[key]
                for nm, v in new_aux_loc.items():
                    out[f"aux:{nm}"] = v
                return out
            return seg_fn

        carry = {}
        for s, seg_nodes in enumerate(segments):
            carry = jax.checkpoint(make_seg(s, seg_nodes))(carry, rng)
        new_aux = {k[4:]: v for k, v in carry.items()
                   if k.startswith("aux:")}
        outputs = emit_outputs(
            lambda key: carry[f"{key[0]}:{key[1]}"], arg_vals, aux_vals)
        return outputs, new_aux

    return run


def reads_rng(symbol):
    """Does any op of the graph read the random key? ``_exec_node``
    folds the key in for an op that declares ``need_rng`` and hands
    every other op None, so the declaration is the whole answer; a node
    whose op cannot be looked up counts as reading it."""
    try:
        return any(not n.is_variable and n.opdef().need_rng
                   for n in symbol._topo_nodes())
    except MXNetError:
        return True


@functools.lru_cache(maxsize=None)
def _unread_key():
    """The key argument of a program none of whose ops reads one: made
    once, of a fresh key's shape, dtype and (un)placement, so that the
    program's signature is what it is under a drawn key."""
    return jax.random.PRNGKey(0)


class Executor:
    """reference: include/mxnet/executor.h + python/mxnet/executor.py."""

    def __init__(self, symbol, ctx, args, args_grad=None, grad_req="write",
                 aux_states=None, group2ctx=None, shared_exec=None,
                 compute_dtype=None, mirror=None, validate=None,
                 mesh_token=None, spmd_plan=None, n_devices=1):
        self._symbol = symbol
        self._ctx = ctx
        # the binding's SpmdPlan (spmd exec groups): threaded into the
        # kernel tier so plan-dependent lowerings (the attention op's
        # sequence-sharded ring variant) can be selected at trace time
        self._spmd_plan = spmd_plan
        # device-topology token for the program-cache key: compiled
        # programs bake in their mesh's collective structure (psum /
        # reduce-scatter shard counts), so a binding over a different
        # mesh or device must never reuse them. Exec groups pass their
        # mesh/plan token; direct bindings key on the single device.
        self._mesh_token = mesh_token if mesh_token is not None else \
            ("dev", ctx.device_type, int(getattr(ctx, "device_id", 0)))
        self._group2ctx = group2ctx or {}
        self._compute_dtype = compute_dtype
        self._monitor_callback = None
        self.output_names = symbol.list_outputs()

        # gradient mirroring (reference: MXNET_BACKWARD_DO_MIRROR,
        # graph_executor.cc:210-223): True -> sqrt(N) segments under
        # jax.checkpoint; an int picks the segment count explicitly
        if mirror is None:
            import os as _os
            mirror = _os.environ.get("MXNET_BACKWARD_DO_MIRROR",
                                     "0").lower() in ("1", "true")
        if mirror is True:
            n_compute = sum(1 for n in symbol._topo_nodes()
                            if not n.is_variable)
            self._remat_segments = max(2, int(np.ceil(np.sqrt(n_compute))))
        elif mirror:
            self._remat_segments = int(mirror)
        else:
            self._remat_segments = 0

        # ---- normalize arg arrays -------------------------------------
        arg_names_all = symbol.list_arguments()
        self.arg_arrays = self._normalize_args(args, arg_names_all, "args")

        # resolve init-op nodes declared with unknown (0) dims — e.g. RNN
        # begin_state zeros(shape=(0, H)) — against the bound arg shapes
        shape_overrides = {}
        try:
            known = {nm: tuple(a.shape)
                     for nm, a in zip(arg_names_all, self.arg_arrays)
                     if a is not None}
            needs = [n for n in symbol._topo_nodes()
                     if not n.is_variable and not n.inputs
                     and isinstance(n.attrs.get("shape"), tuple)
                     and 0 in n.attrs["shape"]]
            if needs:
                entry_shapes = symbol._infer_entry_shapes(known)
                for n in needs:
                    s = entry_shapes[id(n)][0]
                    if s is not None and 0 not in s:
                        shape_overrides[id(n)] = tuple(s)
        except MXNetError:
            pass

        # model parallelism: ctx_group tags + group2ctx -> mesh shardings
        # (reference AssignContext/PlaceDevice, graph_executor.cc:242-331)
        self._mp_plan = None
        if self._group2ctx:
            from .parallel.placement import build_plan
            shapes_by_name = {nm: tuple(a.shape)
                              for nm, a in zip(arg_names_all, self.arg_arrays)
                              if a is not None}
            self._mp_plan = build_plan(symbol, self._group2ctx,
                                       shapes_by_name)

        self._shape_overrides = shape_overrides
        with _telemetry.span("executor.bind",
                             _hist="executor.bind.seconds",
                             outputs=len(self.output_names)):
            self._runner, self.arg_names, self.aux_names, self._loss_mask = \
                _build_graph_runner(symbol, shape_overrides,
                                    mp_plan=self._mp_plan,
                                    compute_dtype=compute_dtype,
                                    remat_segments=self._remat_segments,
                                    spmd_plan=spmd_plan,
                                    n_devices=n_devices
                                    if self._mp_plan is None
                                    else self._mp_plan.mesh.size)
        # learned once: a graph without an op that reads the key draws
        # none (``forward``), as the imperative path does for such an op
        self._reads_rng = reads_rng(symbol)
        self._draws = _telemetry.metrics.held_counters("executor.rng.draws")
        self.aux_arrays = self._normalize_args(aux_states, self.aux_names,
                                               "aux_states", allow_none=True)
        self.grad_req = self._normalize_req(grad_req)
        self.grad_arrays = self._normalize_grads(args_grad)

        if self._mp_plan is not None:
            # re-place every bound array per the plan (params sharded over
            # the model axis, the rest replicated across the mesh)
            for nm, arr in zip(self.arg_names, self.arg_arrays):
                if arr is not None:
                    arr._set(self._mp_plan.place(nm, arr.asjax()))
            for nm, arr in zip(self.arg_names, self.grad_arrays):
                if arr is not None:
                    arr._set(self._mp_plan.place(nm, arr.asjax()))
            for arr in self.aux_arrays:
                if arr is not None:
                    arr._set(jax.device_put(arr.asjax(),
                                            self._mp_plan.replicated))

        # compiled program cache, two levels: the per-instance dict is
        # the fast path, and cacheable bindings (no model-parallel plan)
        # also consult the process-wide program_cache so rebinds
        # (train→eval, force_rebind, bucketing over a shared_group)
        # reuse traces instead of recompiling per instance
        self.refresh_program_key()
        self._tapped_runner = None   # eager monitored runner (per callback)
        self._naive_runner = None    # NaiveEngine serial replay runner
        self._pending = None      # recorded inputs awaiting execution
        self._outputs = None      # computed output NDArrays
        self._sentinel = None     # optional NaN/Inf tripwire (telemetry)
        # param/grad/aux/output footprint -> registry gauges + flight ring
        self.memory_footprint = _telemetry.memory.record_executor_bind(self)

        # bind-time static analysis (the NNVM InferShape/InferType
        # discipline, analysis/): validate="warn"|"raise" per call, or
        # process-wide via MXNET_GRAPH_VALIDATE.
        from . import analysis as _analysis
        vmode = _analysis.resolve_mode(validate)
        if vmode is not None:
            _analysis.validate_executor(self, vmode)

    # ------------------------------------------------------------ normalize
    def _normalize_args(self, args, names, what, allow_none=False):
        if args is None:
            if allow_none or not names:
                return [None] * len(names)
            raise MXNetError(f"bind requires {what}")
        if isinstance(args, dict):
            out = []
            for nm in names:
                if nm not in args:
                    if allow_none:
                        out.append(None)
                        continue
                    raise MXNetError(f"missing {what} entry {nm!r}")
                out.append(args[nm])
            return out
        args = list(args)
        if len(args) != len(names):
            raise MXNetError(
                f"{what} length {len(args)} != expected {len(names)}")
        return args

    def _normalize_req(self, grad_req):
        if isinstance(grad_req, str):
            return {nm: grad_req for nm in self.arg_names}
        if isinstance(grad_req, (list, tuple)):
            return dict(zip(self.arg_names, grad_req))
        if isinstance(grad_req, dict):
            return {nm: grad_req.get(nm, "null") for nm in self.arg_names}
        raise MXNetError("invalid grad_req")

    def _normalize_grads(self, args_grad):
        if args_grad is None:
            return [None] * len(self.arg_names)
        if isinstance(args_grad, dict):
            return [args_grad.get(nm) for nm in self.arg_names]
        args_grad = list(args_grad)
        if len(args_grad) != len(self.arg_names):
            raise MXNetError("args_grad length mismatch")
        return args_grad

    # ------------------------------------------------------------ dict views
    @property
    def arg_dict(self):
        return dict(zip(self.arg_names, self.arg_arrays))

    @property
    def grad_dict(self):
        return dict(zip(self.arg_names, self.grad_arrays))

    @property
    def aux_dict(self):
        return dict(zip(self.aux_names, self.aux_arrays))

    @property
    def output_dict(self):
        return dict(zip(self.output_names, self.outputs))

    # ------------------------------------------------------------- programs
    def _watched(self):
        return [nm for nm in self.arg_names
                if self.grad_req.get(nm, "null") != "null"]

    def _naive_runner_fn(self):
        """Serial deterministic replay runner for the NaiveEngine debug
        mode: every op executes eagerly (no jit, no XLA fusion) and is
        forced to completion before the next one dispatches — the analog
        of the reference's ``MXNET_ENGINE_TYPE=NaiveEngine`` synchronous
        engine (src/engine/naive_engine.cc; the debugging procedure in
        threaded_engine.h:330-338)."""
        if self._naive_runner is None:
            def tap(node, outs):
                for o in outs:
                    # under jax.vjp the forward replays with tracers;
                    # only concrete arrays can (and need to) block
                    if isinstance(o, jax.Array) and \
                            not isinstance(o, jax.core.Tracer):
                        o.block_until_ready()

            self._naive_runner, *_ = _build_graph_runner(
                self._symbol, self._shape_overrides, tap=tap,
                mp_plan=self._mp_plan,
                compute_dtype=self._compute_dtype)
        return self._naive_runner

    def refresh_program_key(self):
        """(Re)derive the process-wide program-cache key from the bound
        cells as they are now, and drop this binding's traced programs:
        called at construction, and when a parameter cell changes dtype
        after bind (executor_group.adopt_param_dtypes)."""
        self._jit_cache = {}
        self._prog_cache_base = None
        if self._mp_plan is not None:
            return
        from .ops import layout as _layout_mod
        compute_dtype = self._compute_dtype
        try:
            self._prog_cache_base = (
                _progcache.symbol_signature(self._symbol),
                tuple((nm, tuple(a.shape), str(a.dtype))
                      for nm, a in zip(self.arg_names, self.arg_arrays)
                      if a is not None),
                tuple((nm, tuple(a.shape), str(a.dtype))
                      for nm, a in zip(self.aux_names, self.aux_arrays)
                      if a is not None),
                self._ctx.device_type,
                self._mesh_token,
                bool(_layout_mod.layout_opt_enabled()),
                str(compute_dtype) if compute_dtype is not None else None,
                self._remat_segments,
            )
        except Exception:
            pass           # uncacheable binding: per-instance only

    def program_cache_key(self, kind, *extras):
        """Process-wide cache key for one of this binding's programs, or
        None when the binding isn't cacheable (model-parallel plan).
        ``extras`` carries what only this program kind depends on (the
        watched-param set for gradient programs, the optimizer token for
        the fused/scan train steps)."""
        if self._prog_cache_base is None:
            return None
        # the kernel tier is read at trace time (kernel_tier.resolve()
        # inside every op dispatch), so programs traced under different
        # tiers differ even for an identical graph — it must ride every
        # key or a flipped MXNET_KERNEL_TIER reuses stale programs
        return self._prog_cache_base + \
            (("ktier", _kernel_tier.mode()),) + (kind,) + extras

    def program_name(self, kind):
        """The function name a program of this binding is jitted under
        - its kind and the shape of the first argument, by convention
        the data input (``fwd_infer_8x64``) - so that the profiler's
        ``XLA Modules`` line and ``jax.monitoring``'s ``fun_name`` say
        which rung and window ran, not ``jit_prog`` for all of them."""
        first = self.arg_arrays[0] if self.arg_arrays else None
        dims = () if first is None else first.shape
        return kind + ("_" + "x".join(str(d) for d in dims) if dims else "")

    @property
    def donates_aux(self):
        """Does the graph hold an op that asks for its aux arrays to be
        donated to the inference program (``OpDef.donate_aux``)? Then a
        compiled ``fwd_infer`` takes over every aux array of the
        binding: an array read from a cell before a step is deleted by
        it, and the cell holds the new one."""
        return any(not n.is_variable and n.opdef().donate_aux
                   for n in self._symbol._topo_nodes())

    def _get_program(self, kind):
        from . import remat as _remat
        naive = naive_engine_active()
        # the staged gradient program honors the remat policy too (the
        # fused step applies it in executor_group); the policy rides
        # both cache keys so flipping it mid-process re-traces
        remat_policy = _remat.active() if kind == "fwd_bwd" else "none"
        cache_key = (kind, naive, remat_policy)
        fn = self._jit_cache.get(cache_key)
        if fn is not None:
            if _telemetry.enabled():
                _telemetry.counter("executor.jit_cache.hit").inc()
            return fn
        gkey = None
        if not naive:
            extras = (tuple(self._watched()),
                      ("remat", remat_policy)) if kind == "fwd_bwd" \
                else ()
            gkey = self.program_cache_key(kind, *extras)
            if gkey is not None:
                fn = _progcache.get(gkey)
                if fn is not None:
                    # process-wide hit: another binding of the same
                    # signature already traced this program
                    if _telemetry.enabled():
                        _telemetry.counter("executor.jit_cache.hit").inc()
                    self._jit_cache[cache_key] = fn
                    _optable.register_program(self.program_name(kind),
                                              self, kind)
                    return fn
        if _telemetry.enabled():
            _telemetry.counter("executor.jit_cache.miss").inc()
        runner = self._naive_runner_fn() if naive else self._runner
        if kind in ("fwd_infer", "fwd_train"):
            is_train = kind == "fwd_train"
            # a graph whose decode state is large and updated a few rows
            # a step (OpDef.donate_aux) hands its aux arrays over to the
            # inference program, which updates them in place; an aux the
            # program does not rewrite goes back as it came
            donate = kind == "fwd_infer" and not naive and self.donates_aux

            def prog(arg_vals, aux_vals, rng):
                outs, new_aux = runner(arg_vals, aux_vals, is_train, rng)
                if donate:
                    new_aux = {**aux_vals, **new_aux}
                return outs, new_aux

            prog.__name__ = self.program_name(kind)
            fn = _telemetry.wrap_dispatch(prog, kind, compiled=False) \
                if naive else _telemetry.wrap_dispatch(
                    jax.jit(prog, donate_argnums=(1,) if donate else ()),
                    kind)
        elif kind == "fwd_bwd":
            watched = self._watched()

            def prog(arg_vals, aux_vals, rng, head_grads):
                w = {nm: arg_vals[nm] for nm in watched}
                rest = {nm: v for nm, v in arg_vals.items()
                        if nm not in w}

                def f(wvals):
                    outs, new_aux = runner({**rest, **wvals}, aux_vals,
                                           True, rng)
                    return outs, new_aux

                f = _remat.wrap(f, remat_policy)
                outs, vjp_fn, new_aux = jax.vjp(f, w, has_aux=True)
                grads, = vjp_fn(head_grads)
                return outs, new_aux, grads

            prog.__name__ = self.program_name(kind)
            fn = _telemetry.wrap_dispatch(prog, kind, compiled=False) \
                if naive else _telemetry.wrap_dispatch(jax.jit(prog), kind)
        else:
            raise ValueError(kind)
        if gkey is not None:
            _progcache.put(gkey, fn)
        self._jit_cache[cache_key] = fn
        if not naive:
            _optable.register_program(self.program_name(kind), self, kind)
        return fn

    def _default_heads(self, arg_vals):
        """Head gradients where the caller gives none: ones for loss
        heads (their custom_vjp ignores the value), zeros for data
        heads -> no spurious gradient."""
        outs_struct = jax.eval_shape(
            lambda a, x, r: self._runner(a, x, True, r)[0],
            arg_vals, self._aux_vals(), jax.random.PRNGKey(0))
        return [jnp.ones(o.shape, o.dtype) if is_loss
                else jnp.zeros(o.shape, o.dtype)
                for o, is_loss in zip(outs_struct, self._loss_mask)]

    def lower_program(self, kind):
        """``jax.stages.Lowered`` of this binding's program ``kind``
        (``fwd_infer``, ``fwd_train``, ``fwd_bwd``) at the bound cells'
        shapes and placements - for the compiled text
        (``telemetry/optable.py``'s op index) and for cost and memory
        analysis. Nothing runs and no cell changes."""
        prog = self._get_program(kind)
        if not hasattr(prog, "lower"):
            raise MXNetError("a NaiveEngine program is not compiled: "
                             "there is nothing to lower")
        arg_vals = self._arg_vals()
        rng = self._pending[1] if self._pending else _unread_key()
        args = [arg_vals, self._aux_vals(), rng]
        if kind == "fwd_bwd":
            args.append(self._default_heads(arg_vals))
        return prog.lower(*args)

    # -------------------------------------------------------------- forward
    def forward(self, is_train=False, **kwargs):
        """Set optional input kwargs and run (lazily when training).

        reference: python/mxnet/executor.py forward / MXExecutorForward.
        """
        if kwargs:
            ad = self.arg_dict
            for nm, val in kwargs.items():
                if nm not in ad:
                    raise MXNetError(f"unknown forward argument {nm!r}")
                if isinstance(val, NDArray):
                    ad[nm]._set(val.asjax().astype(ad[nm].dtype))
                else:
                    ad[nm]._set(jnp.asarray(val, dtype=ad[nm].dtype))
        rng = self._next_key()
        self._pending = ("fwd_train" if is_train else "fwd_infer", rng)
        self._outputs = None
        if not is_train:
            self._materialize_outputs()
            return self.outputs
        # training: stay lazy so backward() costs exactly one fused
        # fwd+bwd execution; the returned view materializes on access
        return _LazyOutputs(self)

    def _next_key(self):
        """The key of one forward: a fresh one off ``mx.random``'s host
        chain (``executor.rng.draws``) where an op of the graph reads
        it, training or not; else one constant, and the chain stays
        where it was - a split is two one-operation programs on the
        device."""
        if not self._reads_rng:
            return _unread_key()
        if _telemetry.enabled():
            self._draws()[0].inc()
        return _random.next_key()

    def _arg_vals(self):
        return {nm: a.asjax() for nm, a in zip(self.arg_names,
                                               self.arg_arrays)}

    def _aux_vals(self):
        return {nm: a.asjax() for nm, a in zip(self.aux_names,
                                               self.aux_arrays)}

    def _run_tapped(self, is_train, rng):
        """Monitored execution: walk the graph eagerly (un-jitted) and
        tap every op's outputs — full parity with the reference's
        ExecuteMonCallback granularity (graph_executor.cc:758-778), at
        interpreter speed (it's a debug mode there too: bulk exec must
        be off for per-op stats, env_var.md:71)."""
        if self._tapped_runner is None:
            def tap(node, outs):
                out_names = node.output_names() if hasattr(
                    node, "output_names") else None
                for i, o in enumerate(outs):
                    nm = out_names[i] if out_names and i < len(out_names) \
                        else (f"{node.name}_output" if len(outs) == 1
                              else f"{node.name}_output{i}")
                    self._monitor_callback(nm, NDArray(o, ctx=self._ctx))

            self._tapped_runner, *_ = _build_graph_runner(
                self._symbol, self._shape_overrides, tap=tap,
                mp_plan=self._mp_plan,
                compute_dtype=self._compute_dtype)
        return self._tapped_runner(self._arg_vals(), self._aux_vals(),
                                   is_train, rng)

    def _materialize_outputs(self):
        if self._outputs is not None or self._pending is None:
            return
        kind, rng = self._pending
        try:
            if self._monitor_callback is not None:
                outs, new_aux = self._run_tapped(kind == "fwd_train", rng)
                self._finish(outs, new_aux, monitored=True)
                return
            prog = self._get_program(kind)
            outs, new_aux = prog(self._arg_vals(), self._aux_vals(), rng)
            self._finish(outs, new_aux)
        except Exception as exc:
            _telemetry.flightrec.on_crash(exc, where="executor.forward")
            raise

    def _finish(self, outs, new_aux, grads=None, monitored=False):
        self._outputs = [NDArray(o, ctx=self._ctx) for o in outs]
        if new_aux:
            aux_d = self.aux_dict
            for nm, val in new_aux.items():
                aux_d[nm]._set(val)
        if grads is not None:
            gd = dict(zip(self.arg_names, self.grad_arrays))
            for nm, g in grads.items():
                dst = gd.get(nm)
                if dst is None:
                    continue
                req = self.grad_req.get(nm, "null")
                if req == "write":
                    dst._set(g.astype(dst.dtype))
                elif req == "add":
                    dst._set(dst.asjax() + g.astype(dst.dtype))
        if self._monitor_callback is not None and not monitored:
            for nm, arr in zip(self.output_names, self._outputs):
                self._monitor_callback(nm, arr)
        if self._sentinel is not None:
            self._sentinel.check_executor(self, grads_fresh=grads is not None)

    @property
    def outputs(self):
        self._materialize_outputs()
        return self._outputs if self._outputs is not None else []

    def release_outputs(self):
        """Let go of the latest forward's outputs: whoever read them
        owns them now, and their device memory goes when that reader
        drops them, not at this executor's next forward
        (``BatchedKVCacheDecoder.release_outputs``: a window program's
        logits over a vocabulary of 131,072 are 2 GB, and an engine
        holds a program for every rung). ``outputs`` is empty until the
        next ``forward``."""
        self._outputs = None
        self._pending = None

    # -------------------------------------------------------------- backward
    def backward(self, out_grads=None):
        """Propagate gradients (fused fwd+bwd XLA program).

        reference: MXExecutorBackward -> RunOps over the backward segment.
        """
        if self._pending is None:
            raise MXNetError("backward() requires a prior forward(is_train=True)")
        kind, rng = self._pending
        if kind != "fwd_train":
            raise MXNetError("backward() after forward(is_train=False)")
        # head gradients: user-provided, else ones for loss heads
        if out_grads is None:
            heads = None
        elif isinstance(out_grads, NDArray):
            heads = [out_grads]
        else:
            heads = list(out_grads)
        arg_vals = self._arg_vals()
        out_shapes = None
        if heads is None:
            heads = self._default_heads(arg_vals)
        else:
            heads = [h.asjax() if isinstance(h, NDArray) else jnp.asarray(h)
                     for h in heads]
        monitored = self._monitor_callback is not None
        try:
            if monitored and self._outputs is None:
                # training forward is lazy and the gradient path below runs
                # as one fused XLA program, so the per-op tap would
                # otherwise never fire under fit(monitor=...) — replay the
                # forward eagerly (same rng) purely for the monitor's
                # benefit. Skipped when outputs already materialized
                # through the tapped path (a caller that read .outputs
                # after forward) — the taps fired there.
                self._run_tapped(True, rng)
            prog = self._get_program("fwd_bwd")
            outs, new_aux, grads = prog(arg_vals, self._aux_vals(), rng,
                                        heads)
            self._finish(outs, new_aux, grads, monitored=monitored)
        except Exception as exc:
            _telemetry.flightrec.on_crash(exc, where="executor.backward")
            raise
        self._pending = None

    # ------------------------------------------------------------- utilities
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        """reference: executor.py copy_params_from."""
        ad = self.arg_dict
        for nm, arr in arg_params.items():
            if nm in ad:
                ad[nm]._set(jnp.asarray(
                    arr.asnumpy() if isinstance(arr, NDArray) else arr,
                    dtype=ad[nm].dtype))
            elif not allow_extra_params:
                raise MXNetError(f"unknown param {nm!r}")
        if aux_params:
            xd = self.aux_dict
            for nm, arr in aux_params.items():
                if nm in xd:
                    xd[nm]._set(jnp.asarray(
                        arr.asnumpy() if isinstance(arr, NDArray) else arr,
                        dtype=xd[nm].dtype))
                elif not allow_extra_params:
                    raise MXNetError(f"unknown aux param {nm!r}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Re-bind with new shapes (fresh XLA programs compile on demand)."""
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**kwargs)
        new_args = {}
        for nm, s, old in zip(self.arg_names, arg_shapes, self.arg_arrays):
            if tuple(old.shape) == tuple(s):
                new_args[nm] = old
            else:
                new_args[nm] = nd_zeros(s, ctx=self._ctx, dtype=old.dtype)
        new_grads = {}
        for nm, s, old in zip(self.arg_names, arg_shapes, self.grad_arrays):
            if old is None:
                continue
            new_grads[nm] = old if tuple(old.shape) == tuple(s) else \
                nd_zeros(s, ctx=self._ctx, dtype=old.dtype)
        new_aux = {}
        for nm, s, old in zip(self.aux_names, aux_shapes, self.aux_arrays):
            new_aux[nm] = old if tuple(old.shape) == tuple(s) else \
                nd_zeros(s, ctx=self._ctx, dtype=old.dtype)
        return Executor(self._symbol, self._ctx, new_args, new_grads,
                        self.grad_req, new_aux, self._group2ctx,
                        compute_dtype=self._compute_dtype,
                        mirror=self._remat_segments or 0)

    def cost_table(self, train=None):
        """Per-op FLOPs/bytes attribution for this binding's shapes
        (telemetry/mfu.py). ``train`` defaults to whether gradients are
        watched. Returns None when shapes can't be inferred."""
        from .telemetry import mfu as _mfu
        if train is None:
            train = bool(self._watched())
        shapes = {nm: tuple(a.shape)
                  for nm, a in zip(self.arg_names, self.arg_arrays)
                  if a is not None}
        try:
            return _mfu.cost_table(self._symbol, shapes, train=train)
        except Exception:
            return None

    def set_monitor_callback(self, callback):
        self._monitor_callback = callback
        self._tapped_runner = None  # tap closure binds the callback

    def debug_str(self):
        lines = [f"Symbol outputs: {self.output_names}"]
        for node in self._symbol._topo_nodes():
            kind = "var" if node.is_variable else node.op
            lines.append(f"  {kind:<20} {node.name}")
        return "\n".join(lines)

    # ----------------------------------------------------------- simple_bind
    @staticmethod
    def _simple_bind(symbol, ctx, grad_req, type_dict, group2ctx, shapes,
                     mirror=None, validate=None):
        arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        type_dict = type_dict or {}
        # a variable's declared __dtype__ binds a cell of that dtype
        # (the int8 quant tier's _q weights; executor_group does the
        # same — analysis rule GV105 audits the declaration either way);
        # an explicit type_dict entry wins
        declared = {n.name: np.dtype(n._extra["__dtype__"])
                    for n in symbol._topo_nodes()
                    if n.is_variable and n._extra.get("__dtype__")}
        args = {}
        for nm, s in zip(arg_names, arg_shapes):
            args[nm] = nd_zeros(s, ctx=ctx,
                                dtype=type_dict.get(
                                    nm, declared.get(nm, np.float32)))
        req = grad_req if isinstance(grad_req, dict) else \
            {nm: grad_req for nm in arg_names}
        grads = {nm: nd_zeros(s, ctx=ctx, dtype=type_dict.get(nm, np.float32))
                 for nm, s in zip(arg_names, arg_shapes)
                 if req.get(nm, "null") != "null"}
        # aux cells honor a declared dtype too (attention_decode's int32
        # cache cursor) — same GV105 discipline as the arg cells
        aux = {nm: nd_zeros(s, ctx=ctx,
                            dtype=declared.get(nm, np.float32))
               for nm, s in zip(aux_names, aux_shapes)}
        return Executor(symbol, ctx, args, grads, grad_req, aux, group2ctx,
                        mirror=mirror, validate=validate)
