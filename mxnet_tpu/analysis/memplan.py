"""Static memory planner (ME8xx): peak HBM per binding, before compile.

The only memory evidence this framework had was *runtime*: the NDArray
ledger (telemetry/memory.py) after arrays exist, and the fused step's
``remat.residual_bytes`` — an ``eval_shape`` trace that needs a bound
module and an armed optimizer. This module predicts the same bill from
the Symbol graph alone: a liveness/residual analysis over the executor's
topo order, layout-aware for everything that now decides the footprint —
per-``MXNET_REMAT_POLICY`` residual sets (mirroring the measured
``remat.residual_bytes`` semantics op by op, see below), dtype-aware
param bytes (int8/fp8 quant weights and fp8 KV-cache cells count
1 B/elem), ZeRO's 1/N flat state
shards, SPMD param specs, donation credits, and the batch buffers —
divided across the mesh. Zero compiles, zero traces, no jax import.

Residual model (validated against ``jax.vjp`` + ``eval_shape`` on the
bundled models; the tier-1 agreement gate pins resnet20 within 5% for
all three policies):

* ``none`` — the saved set is the union of per-op saves, deduplicated
  at the *entry* (node-output) level exactly as partial-eval residuals
  are: conv/dense save their data input (grad_w needs it), BatchNorm
  saves its input plus the normalized copy (when gradient actually
  flows), activations save their input, elementwise adds / pooling /
  movement save nothing, loss heads save their output (the custom-vjp
  ``(prob, label)`` pair) — plus every backward-reachable param;
* ``dots`` — ``remat.DOT_SAVEABLE_OPS`` outputs + program inputs
  (params + batch): the static mirror of
  ``jax.checkpoint_policies.dots_saveable`` as read from jax 0.9.0
  (it saves the outputs of ``dot_general``, ``conv_general_dilated``
  and ``scaled_matmul_wrapper`` and nothing else). Each saved entry
  counts once, as ``remat.residual_bytes`` counts it: a value that a
  nested ``jit`` hands back unchanged is the entry that went in;
* ``all`` — program inputs only (params + batch).

Surfaces: ``mxlint --memory-plan <model> --policy dots --batch 256``,
``DataParallelExecutorGroup.static_memory_plan()`` (the batch-bucket
headroom gate's static fast path, cross-checked against the eval_shape
number in tests), the ``memory_planner`` analysis pass (ME801
predicted-OOM, ME802 headroom-admits-larger-bucket) and a "memory plan"
section in ``tools/diagnose.py`` via the ``memplan.*`` gauges.
"""
from __future__ import annotations

import numpy as np

from .diagnostics import Diagnostic
from .precision import entry_dtypes, dtype_name, _label_names

__all__ = ["OPTIMIZER_STATE_MULT", "state_multiplier", "plan_symbol",
           "plan_findings", "record_plan", "format_plan"]

#: optimizer -> param-shaped f32 state arrays the fused plan carries
OPTIMIZER_STATE_MULT = {
    "sgd": 1.0,            # momentum buffer (mom=0 still allocates it)
    "sgd_mom": 1.0, "nag": 1.0, "ccsgd": 1.0, "sgld": 0.0,
    "adam": 2.0, "rmsprop": 1.0, "rmspropalex": 2.0,
    "adagrad": 1.0, "adadelta": 2.0, "ftrl": 2.0,
}

#: per-op residual behavior under policy "none" (see module docstring)
_SAVE_INPUT0_FOR_GRAD_W = frozenset({
    "Convolution", "Deconvolution", "FullyConnected", "dot", "batch_dot",
    "QuantizedFullyConnected", "QuantizedConvolution", "RNN",
    "FusedConvBNReLU", "attention",
})
_SAVE_INPUT0_IF_GRAD = frozenset({
    "Activation", "LeakyReLU", "softmax", "log_softmax",
    "SoftmaxActivation", "sigmoid", "tanh", "relu", "clip", "square",
    "sqrt", "rsqrt", "exp", "log", "FusedBiasGeLU", "L2Normalization",
    "InstanceNorm", "LRN",
})
_NORM_OPS = frozenset({"BatchNorm", "LayerNorm"})
_SAVE_ALL_INPUTS_IF_GRAD = frozenset({
    "_mul", "elemwise_mul", "broadcast_mul", "_div", "elemwise_div",
    "broadcast_div", "_power", "broadcast_power", "_hypot",
    "broadcast_hypot", "_maximum", "broadcast_maximum", "_minimum",
    "broadcast_minimum",
})
_SAVE_NOTHING = frozenset({
    "_plus", "elemwise_add", "broadcast_add", "_minus", "elemwise_sub",
    "broadcast_sub", "Flatten", "flatten", "Reshape",
    "reshape", "transpose", "Cast", "cast", "_copy", "identity",
    "BlockGrad", "stop_gradient", "Concat", "concat", "SliceChannel",
    "split", "slice", "slice_axis", "expand_dims", "Embedding",
    "one_hot", "_zeros", "_ones", "_arange", "add_n",
    # RoPE is linear in x (fixed-angle rotation): its vjp is the inverse
    # rotation, no activation saved beyond the (T, D/2) trig tables;
    # attention_decode is inference-only (never differentiated)
    "RoPE", "attention_decode",
})


def state_multiplier(optimizer):
    """f32 param-shaped state arrays for one optimizer (by name or
    instance); unknown optimizers estimate 1."""
    name = optimizer if isinstance(optimizer, str) else \
        type(optimizer).__name__
    return OPTIMIZER_STATE_MULT.get(str(name).lower(), 1.0)


def _opdef_of(node):
    try:
        return node.opdef()
    except Exception:
        return None


def _nelems(shape):
    n = 1
    for d in shape:
        n *= int(d)
    return n


def _itemsize(name):
    # fp8 storage (quant weights, KV cache cells) is 1 B/elem; resolve
    # it by name so the no-jax contract holds even when ml_dtypes has
    # not registered the dtype with numpy
    if str(name).startswith("float8"):
        return 1
    try:
        return np.dtype(name).itemsize
    except TypeError:
        return 4


def _default_prefix_cache_bytes():
    """The serve-plane prefix store's byte budget, charged only when
    the operator armed it (``MXNET_SERVE_PREFIX_CACHE_MB`` set in the
    environment): plans for non-serving bindings stay byte-identical."""
    import os
    raw = os.environ.get("MXNET_SERVE_PREFIX_CACHE_MB")
    if raw is None:
        return 0
    try:
        return int(max(0.0, float(raw)) * (1 << 20))
    except ValueError:
        return 0


def plan_symbol(symbol, shapes, policy="none", for_training=True,
                optimizer="sgd_mom", compute_dtype=None, n_data=1,
                spmd_plan=None, zero=False, donation=True,
                fixed_params=(), state_bytes=None, batch_axis=0,
                prefix_cache_bytes=None):
    """Static peak-HBM plan for one (symbol, input shapes) binding.

    ``shapes`` maps data/label names to concrete shapes (the same dict
    ``infer_shape``/``simple_bind`` take) — those names classify as
    batch buffers, every other argument as a parameter. Returns a plan
    dict; raises MXNetError only when shape inference itself fails.

    ``n_data`` divides the batch-linear components (batch, activations,
    outputs) for the per-device view; ``spmd_plan`` (a
    ``parallel.spmd.SpmdPlan``) additionally shards param/state bytes
    per its PartitionSpecs; ``zero`` shards optimizer state 1/N over the
    data axis (ZeRO-1's flat layout). ``state_bytes`` overrides the
    optimizer-multiplier estimate with an exact figure (the exec group
    knows its armed state tree). ``donation=False`` adds the
    double-buffer params+state a non-donating (staged) update pays.

    ``prefix_cache_bytes`` charges the serving prefix store's byte
    budget (``serve.prefix.PrefixStore``) against slot-pooled decode
    bindings — ``None`` reads ``MXNET_SERVE_PREFIX_CACHE_MB`` when set
    (else 0), so ME801 gates HBM with the store's worst case included
    before anything compiles. The charge applies only to graphs with a
    ``per_slot`` stateful decode op (the store snapshots their rows).
    """
    shapes = dict(shapes)
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**shapes)
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    known = dict(zip(arg_names, arg_shapes))
    known.update(zip(aux_names, aux_shapes))
    entry_shapes = symbol._infer_entry_shapes(known)
    dtypes = entry_dtypes(symbol, compute_dtype=compute_dtype)

    nodes = symbol._topo_nodes()
    by_id = {id(n): n for n in nodes}

    def entry_bytes(key):
        node = by_id.get(key[0])
        store = entry_shapes.get(key[0])
        s = store[key[1]] if store and key[1] < len(store) else None
        if node is not None and node.is_variable and s is None:
            s = known.get(node.name)
        if s is None or 0 in tuple(s):
            return 0
        return _nelems(s) * _itemsize(dtypes.get(key, "float32"))

    # labels ride the batch even when the caller seeded only the data
    # shape (inference fills them in): never classify them as params
    batch_names = set(shapes) | _label_names(symbol)
    param_nodes = [n for n in nodes if n.is_variable
                   and not n._extra.get("__is_aux__")
                   and n.name not in batch_names]
    watched = [n for n in param_nodes if n.name not in set(fixed_params)
               and dtypes.get((id(n), 0)) not in ("int8",
                                                  "float8_e4m3fn",
                                                  "float8_e5m2")]

    def shard_fraction(name, shape):
        if spmd_plan is None:
            return 1.0
        try:
            frac = spmd_plan.param_shard_fraction(name, shape)
        except Exception:
            frac = 1.0
        return frac

    param_bytes = sum(
        int(entry_bytes((id(n), 0)) * shard_fraction(
            n.name, known.get(n.name) or ()))
        for n in param_nodes)
    watched_f32 = sum(_nelems(known[n.name]) * 4 for n in watched
                      if known.get(n.name))
    batch_bytes = sum(entry_bytes((id(n), 0)) for n in nodes
                      if n.is_variable and n.name in batch_names)
    aux_bytes = sum(_nelems(s) * 4 for s in aux_shapes if s is not None)
    # KV-cache accounting: a stateful-inference op's aux states (the
    # fixed-capacity K/V cache + cursor) are the decode path's dominant
    # resident bytes — charge them into the per-op table so the plan
    # names WHERE the HBM goes, not just that aux is big
    kv_charges = []
    for n in nodes:
        if n.is_variable:
            continue
        try:
            opdef = n.opdef()
        except Exception:
            continue
        if not getattr(opdef, "stateful_infer", False):
            continue
        aux_n = len(opdef.aux_names(n.attrs))
        if not aux_n:
            continue
        nb = 0
        for inp, idx in n.inputs[len(n.inputs) - aux_n:]:
            store = known.get(inp.name)
            if store is not None and 0 not in tuple(store):
                nb += _nelems(store) * _itemsize(
                    dtypes.get((id(inp), idx), "float32"))
        kv_charges.append((n.op, nb))
    kv_cache_bytes = sum(nb for _, nb in kv_charges)
    # prefix-store accounting: the serving plane's prefix cache holds
    # snapshots of these same rows under its own byte budget — a
    # slot-pooled decode binding pays the full budget up front so ME801
    # trips BEFORE the store could grow into an OOM
    from ..base import parse_bool as _parse_bool
    per_slot_decode = any(
        not n.is_variable and _parse_bool(n.attrs.get("per_slot", False))
        and getattr(_opdef_of(n), "stateful_infer", False)
        for n in nodes)
    prefix_store_bytes = 0
    if per_slot_decode and kv_charges:
        prefix_store_bytes = int(prefix_cache_bytes
                                 if prefix_cache_bytes is not None
                                 else _default_prefix_cache_bytes())
    output_bytes = sum(_nelems(s) * 4 for s in out_shapes
                       if s is not None)

    per_op_bytes = {}

    def charge(op, nbytes):
        if nbytes:
            per_op_bytes[op] = per_op_bytes.get(op, 0) + int(nbytes)

    for _op, _nb in kv_charges:
        charge(_op, _nb)
    charge("prefix_store", prefix_store_bytes)

    residual = 0
    if for_training:
        residual = _residual_bytes(
            nodes, entry_bytes, policy,
            watched={n.name for n in watched},
            batch_names=batch_names,
            param_bytes=sum(entry_bytes((id(n), 0))
                            for n in param_nodes),
            batch_bytes=batch_bytes, charge=charge)

    grad_bytes = watched_f32 if for_training else 0
    if state_bytes is None:
        state_bytes = (state_multiplier(optimizer) * watched_f32
                       if for_training else 0)
    state_bytes = int(state_bytes)
    n_state_shards = max(1, int(n_data)) if zero else 1
    state_dev = state_bytes // n_state_shards
    nd = max(1, int(n_data))

    fixed_dev = param_bytes + state_dev + aux_bytes + prefix_store_bytes
    linear_dev = (batch_bytes + residual + output_bytes) // nd
    peak_dev = fixed_dev + grad_bytes + linear_dev
    if for_training and not donation:
        peak_dev += param_bytes + state_dev     # staged double-buffer

    batch_size = None
    for name in shapes:
        s = shapes[name]
        if s and len(s) > batch_axis:
            batch_size = int(s[batch_axis])
            break
    per_sample = ((residual + batch_bytes) / batch_size
                  if batch_size else None)

    return {
        "policy": policy,
        "for_training": bool(for_training),
        "batch_size": batch_size,
        "n_data": nd,
        "zero": bool(zero),
        "param_bytes": int(param_bytes),
        "grad_bytes": int(grad_bytes),
        "state_bytes": int(state_bytes),
        "state_bytes_per_device": int(state_dev),
        "aux_bytes": int(aux_bytes),
        "kv_cache_bytes": int(kv_cache_bytes),
        "prefix_store_bytes": int(prefix_store_bytes),
        "batch_bytes": int(batch_bytes),
        "residual_bytes": int(residual),
        "output_bytes": int(output_bytes),
        "fixed_bytes": int(fixed_dev),
        "per_sample_bytes": per_sample,
        "peak_bytes_per_device": int(peak_dev),
        "per_op_bytes": per_op_bytes,
    }


def _residual_bytes(nodes, entry_bytes, policy, watched, batch_names,
                    param_bytes, batch_bytes, charge):
    """Policy-conditional residual set (see module docstring)."""
    by_id = {id(n): n for n in nodes}
    from .. import remat as _remat
    if policy == "all":
        return param_bytes + batch_bytes
    if policy == "dots":
        total = param_bytes + batch_bytes
        for n in nodes:
            if n.is_variable or n.op not in _remat.DOT_SAVEABLE_OPS:
                continue
            nb = entry_bytes((id(n), 0))
            charge(n.op, nb)
            total += nb
        return total

    # policy "none": entry-level saved-set walk with dedup
    needs_grad = {}
    for n in nodes:
        if n.is_variable:
            needs_grad[id(n)] = n.name in watched
        else:
            needs_grad[id(n)] = any(needs_grad.get(id(inp), False)
                                    for inp, _ in n.inputs)

    saved = {}          # entry key -> charged op (dedup)
    synthetic = 0

    def mark(key, op):
        if key not in saved:
            saved[key] = op

    for n in nodes:
        if n.is_variable:
            continue
        try:
            opdef = n.opdef()
            aux_n = len(opdef.aux_names(n.attrs))
            is_loss = opdef.is_loss
        except Exception:
            aux_n, is_loss = 0, False
        ins = n.inputs[:len(n.inputs) - aux_n] if aux_n else n.inputs
        in0 = ins[0] if ins else None
        op = n.op
        if is_loss:
            nb = entry_bytes((id(n), 0))
            synthetic += nb
            charge(op, nb)
            if len(ins) > 1:
                mark((id(ins[1][0]), ins[1][1]), op)
            continue
        if op in _SAVE_NOTHING:
            continue
        if op in _SAVE_INPUT0_FOR_GRAD_W:
            # grad_w needs the data input whenever the weight trains
            trains = any(inp.is_variable and inp.name in watched
                         for inp, _ in ins[1:]) or \
                (in0 is not None and needs_grad.get(id(in0[0]), False))
            if trains and in0 is not None:
                mark((id(in0[0]), in0[1]), op)
            continue
        if op in _NORM_OPS:
            if in0 is None:
                continue
            x_key = (id(in0[0]), in0[1])
            gamma_trains = any(
                inp.is_variable and inp.name in watched
                for inp, _ in ins[1:])
            from ..base import parse_bool
            fix_gamma = parse_bool(n.attrs.get("fix_gamma", False))
            if needs_grad.get(id(in0[0]), False):
                # grad_x path: x plus the normalized copy stay saved
                mark(x_key, op)
                nb = entry_bytes(x_key)
                synthetic += nb
                charge(op, nb)
            elif gamma_trains and not fix_gamma:
                nb = entry_bytes(x_key)     # x-hat only (grad_gamma)
                synthetic += nb
                charge(op, nb)
            continue
        if op == "Pooling":
            # max pooling re-derives its argmax from the saved input
            # during backward; avg/sum pool gradients are input-free
            if str(n.attrs.get("pool_type", "max")) == "max" and \
                    in0 is not None and \
                    needs_grad.get(id(in0[0]), False):
                mark((id(in0[0]), in0[1]), op)
            continue
        if op in _SAVE_INPUT0_IF_GRAD:
            if in0 is not None and needs_grad.get(id(in0[0]), False):
                mark((id(in0[0]), in0[1]), op)
            continue
        if op in _SAVE_ALL_INPUTS_IF_GRAD:
            if needs_grad.get(id(n), False):
                for inp, idx in ins:
                    mark((id(inp), idx), op)
            continue
        if op == "Dropout":
            nb = entry_bytes((id(n), 0))    # the kept-mask
            synthetic += nb
            charge(op, nb)
            continue
        # unknown op: conservative — save its data input when gradient
        # flows through it (the dominant vjp pattern)
        if in0 is not None and needs_grad.get(id(in0[0]), False):
            mark((id(in0[0]), in0[1]), op)

    total = synthetic
    for key, op in saved.items():
        src = by_id.get(key[0])
        # params are counted once via the param_bytes term below
        if src is not None and src.is_variable and \
                src.name not in batch_names:
            continue
        nb = entry_bytes(key)
        charge(op, nb)
        total += nb
    # every backward-reachable param is a residual leaf too (weights
    # feed grad_x, gamma feeds the BN backward)
    total += param_bytes
    return total


def plan_findings(plan, capacity_bytes=None, buckets=None, where=""):
    """ME8xx diagnostics for one plan against a device capacity."""
    found = []
    if not capacity_bytes:
        return found
    peak = plan["peak_bytes_per_device"]
    tag = f" ({where})" if where else ""
    if peak > capacity_bytes:
        found.append(Diagnostic(
            "ME801", f"predicted peak {peak / 1e9:.2f} GB exceeds the "
            f"device capacity {capacity_bytes / 1e9:.2f} GB at batch "
            f"{plan['batch_size']} under policy "
            f"{plan['policy']!r}{tag}",
            hint="shrink the batch bucket, pick a stronger remat "
                 "policy (dots/all), enable ZeRO, or shard params "
                 "(mxlint --memory-plan compares policies statically)"))
        return found
    if buckets and plan.get("per_sample_bytes"):
        from ..telemetry.memory import batch_headroom
        fixed = plan["fixed_bytes"] + plan["grad_bytes"]
        admitted = batch_headroom(capacity_bytes, fixed,
                                  plan["per_sample_bytes"], buckets)
        if admitted and plan["batch_size"] and \
                admitted > plan["batch_size"]:
            found.append(Diagnostic(
                "ME802", f"headroom admits batch {admitted} (now "
                f"{plan['batch_size']}) under policy "
                f"{plan['policy']!r}: "
                f"{(capacity_bytes - peak) / 1e9:.2f} GB spare{tag}",
                hint="raise the batch bucket to claim the remat/ZeRO-"
                     "freed HBM (docs/performance.md)"))
    return found


def record_plan(plan, model=""):
    """Mirror a plan into telemetry (memplan.* gauges + a flight-ring
    note) so tools/diagnose.py renders a 'memory plan' section."""
    try:
        from .. import telemetry as _telemetry
        labels = {"policy": plan["policy"]}
        if model:
            labels["model"] = model
        for key in ("peak_bytes_per_device", "residual_bytes",
                    "param_bytes", "state_bytes", "batch_bytes"):
            _telemetry.gauge(f"memplan.{key}", **labels).set(plan[key])
        _telemetry.flightrec.note(
            "memplan.plan", model=model, policy=plan["policy"],
            batch=plan["batch_size"] or 0,
            peak_bytes=plan["peak_bytes_per_device"],
            residual_bytes=plan["residual_bytes"])
    except Exception:   # telemetry must never break planning
        pass
    return plan


def format_plan(plan, model="", capacity_bytes=None):
    """Human-readable plan section (mxlint/diagnose rendering)."""
    mb = 1.0 / (1 << 20)

    def f(k):
        return f"{plan[k] * mb:10.2f} MiB"

    head = f"memory plan{f' for {model}' if model else ''}: " \
           f"policy={plan['policy']} batch={plan['batch_size']} " \
           f"devices={plan['n_data']}" \
           f"{' zero' if plan['zero'] else ''}"
    lines = [head,
             f"  params        {f('param_bytes')}",
             f"  grads         {f('grad_bytes')}",
             f"  opt state     {f('state_bytes_per_device')}"
             f"{' (1/%d shard)' % plan['n_data'] if plan['zero'] else ''}",
             f"  batch         {f('batch_bytes')}",
             f"  residuals     {f('residual_bytes')}",
             f"  outputs+aux   "
             f"{(plan['output_bytes'] + plan['aux_bytes']) * mb:10.2f}"
             " MiB",
             f"  peak/device   {f('peak_bytes_per_device')}"]
    if capacity_bytes:
        frac = plan["peak_bytes_per_device"] / capacity_bytes
        lines.append(f"  capacity      {capacity_bytes * mb:10.2f} MiB "
                     f"({frac:.0%} used)")
    return "\n".join(lines)
