"""The single operator registry.

The reference has *two* op worlds — legacy ``OperatorProperty`` layer ops and
NNVM ``FCompute`` tensor ops (reference: include/mxnet/operator.h:34-546,
include/mxnet/op_attr_types.h:33-63) — dual-compiled for cpu/gpu against
mshadow templates. Here there is exactly ONE registry: every op is a pure JAX
function plus declarative metadata. XLA replaces mshadow (kernel codegen,
fusion, memory planning) and the same definition serves:

  * imperative NDArray calls (``mx.nd.Convolution(...)``) — the JAX fn runs
    eagerly (async dispatch gives the engine-like pipelining for free);
  * symbolic Symbol nodes (``mx.sym.Convolution(...)``) — the executor traces
    the same fn under ``jax.jit`` so the whole graph compiles to one XLA
    program (the analog of the reference's bulk-exec segments,
    graph_executor.cc:678-756);
  * gradient construction — ``jax.vjp`` of the composed graph replaces the
    NNVM ``Gradient`` pass + per-op ``FGradient`` registrations.

Op forward signature (the "FCompute" of this framework):

    forward(attrs, inputs, aux, is_train, rng) -> (outputs, new_aux)

where ``attrs`` is the typed param dict, ``inputs``/``aux`` are lists of
jax.Arrays, and outputs/new_aux are lists of jax.Arrays. Most ops register a
*simple* forward ``fn(attrs, *inputs) -> array|tuple`` and are wrapped.

Like the reference's ``_init_ndarray_module``/``_init_symbol_module``
(python/mxnet/ndarray.py:875, symbol.py:1585), the user-facing ``mx.nd.*`` and
``mx.sym.*`` functions are auto-generated from this registry at import time.
"""
from __future__ import annotations

from ..base import MXNetError

__all__ = ["OpDef", "register", "get_op", "list_ops", "OP_REGISTRY",
           "read_counts"]

OP_REGISTRY = {}


class OpDef:
    """Metadata + kernel for one operator.

    Parameters
    ----------
    name : canonical op name (the public API surface name).
    forward : full-signature forward (attrs, inputs, aux, is_train, rng).
    inputs : list of input names, or callable(attrs)->list for variadic ops
        (e.g. Concat's num_args; reference: ListArguments()).
    aux : auxiliary-state names (BatchNorm moving stats; reference:
        ListAuxiliaryStates()).
    num_outputs : int or callable(attrs)->int.
    output_names : list or callable(attrs)->list (reference: FListOutputNames).
    attr_spec : dict name -> (parser, default). Unknown kwargs are kept
        verbatim (MXNet tolerates extra attrs in JSON round-trips).
    infer_shape : optional fn(attrs, in_shapes)->(in_shapes, out_shapes,
        aux_shapes) for bidirectional inference (weight shapes deduced from
        data, reference: per-op InferShape); a third ``out_known`` parameter
        is detected at registration. When absent, shapes are derived
        by abstract evaluation (jax.eval_shape) which requires complete
        input shapes. Signatures are validated at registration time
        (malformed arity fails fast with the op name, instead of lazily
        at the first symbol.infer_shape walk).
    infer_type : optional fn(attrs, in_types)->(in_types, out_types,
        aux_types).
    shape_passthrough : declares the op shape-identity on its first input
        (all outputs take input 0's shape) without a dedicated infer fn —
        the explicit opt-out the graph verifier (analysis rule GV107)
        accepts in place of ``infer_shape``, so an op can never *silently*
        fall back to abstract evaluation that stalls on partial shapes.
    variants : alternative kernel implementations keyed by tier name
        (today: ``"pallas"``). ``forward`` is always the XLA composition
        and the fallback of last resort; the kernel-tier selection layer
        (kernel_tier.py) picks per (backend, shape, dtype) under
        ``MXNET_KERNEL_TIER``. Values are full-signature forwards or
        ``(forward, eligible)`` pairs where ``eligible(attrs, in_shapes,
        in_dtypes) -> bool`` gates shapes/attrs the kernel supports.
    flops / bytes_moved : optional cost metadata,
        ``fn(attrs, in_shapes) -> float`` — forward-pass floating-point
        ops and HBM bytes touched for one execution at those input
        shapes. Powers the MFU/roofline telemetry (telemetry/mfu.py);
        ops without it are invisible to MFU accounting (analysis rule
        MF601 lists them).
    need_rng : forward consumes the rng key (Dropout, samplers).
    is_loss : op is a loss head (SoftmaxOutput family) — executor seeds its
        cotangent with ones for backward() with no out_grads.
    mutate_inputs : names of inputs the op writes (optimizer update ops;
        reference: FMutateInputs). Imperative invoke swaps the new buffer
        into the corresponding NDArray handle.
    stateful_infer : the op's aux states are read-AND-written during
        inference forwards too (the KV-cache decode contract) — the
        executor writes ``new_aux`` back even when ``is_train=False``.
        Training aux (BatchNorm moving stats) keeps the train-only rule.
    aux_dtypes : dict aux name -> dtype for aux states that must NOT
        bind as the default float32 cell (a KV cache's int32 position
        cursor). ``symbol._create`` stamps the declaration onto the
        auto-created aux variable (``__dtype__``), and the executor
        binds a cell of that dtype — which also exempts integer aux
        from the mixed-precision entry cast.
    slot_state : for a slot-pooled decode op, ``{aux name: family}``:
        which of its aux cells hold per-slot state and of what kind.
        ``"cursor"`` is the (slots, 1) position every other family is
        read by; ``"rows"`` is a pool with one row per position
        (``[slot, :, position]``: a prefix of it can be captured, copied
        back and reused, and the cursor may be set anywhere below it);
        any other family (``ops/eva.py``'s ``"window"``, ``"summary"``;
        ``"ring"``, the last rows of an op whose ``window`` attribute
        says how many of them it attends)
        is state the cursor alone does not index. The cache driver and
        ``DecodeEngine.migrate`` find the cells through this, never by
        their names.
    state_reads : beside ``slot_state``, ``(counts, reads)``: what one
        execution of the op reads of that state, so that neither the
        cache driver nor the scheduler knows an attention by its name.
        ``counts`` (``read_counts``; or callable(attrs) -> that, as
        ``inputs`` may be) names each count once, with the counter
        ``serve.decode.<counter>`` it increments and the field of the
        ring's ``serve.decode.step`` record it is written to.
        ``reads(attrs, capacity, sources)`` gives ``f(pos, fed) ->
        {count: integer}`` for one execution over pools of ``capacity``
        positions, vectorised over the slots - ``pos`` the (slots,)
        cursors before the dispatch, ``fed`` the tokens each slot is
        fed (0: it reads nothing) - from the host's mirror of the
        cursors alone: no fetch, no synchronisation. ``sources`` is
        ``{input name: attributes}`` of the inputs that are the output
        of another op with ``state_reads`` (a selection and the
        ``topk`` of the indexer that made it). The driver adds the
        dictionaries of a graph's nodes up a dispatch
        (``BatchedKVCacheDecoder.last_reads``). ``reads=None``: the op
        counts on the device, into its one aux cell that is no slot's
        state, a vector in the order of ``counts`` (``MoEFFN``).
    donate_aux : the op's aux arrays are large and updated a few rows
        at a time: the executor donates every aux array of a graph that
        holds such an op to its inference program (``Executor
        .donates_aux``), so that they are updated in place and not
        copied whole. Both decode ops set it (``attention_decode``,
        ``eva_attention_decode``). The contract it puts on the host: an
        array read from an aux cell is deleted by the next step, so
        whatever touches the state between steps reads the cell anew.
    """

    def __init__(self, name, forward, inputs=("data",), aux=(),
                 num_outputs=1, output_names=None, attr_spec=None,
                 infer_shape=None, infer_type=None, need_rng=False,
                 is_loss=False, mutate_inputs=(), num_visible=None,
                 shape_passthrough=False, variants=None, flops=None,
                 bytes_moved=None, stateful_infer=False, aux_dtypes=None,
                 slot_state=None, state_reads=None, donate_aux=False,
                 doc=""):
        self.name = name
        self.forward = forward
        self.variants = {}
        for vname, vfn in (variants or {}).items():
            if isinstance(vfn, tuple):
                self.add_variant(vname, vfn[0], eligible=vfn[1],
                                 kernel_spec=vfn[2]
                                 if len(vfn) > 2 else None)
            else:
                self.add_variant(vname, vfn)
        self.flops = flops
        self.bytes_moved = bytes_moved
        self._inputs = inputs
        self._aux = aux
        self._num_outputs = num_outputs
        self._num_visible = num_visible
        self._output_names = output_names
        self.attr_spec = attr_spec or {}
        self.infer_shape = infer_shape
        self.infer_type = infer_type
        self.need_rng = need_rng
        self.is_loss = is_loss
        self.mutate_inputs = tuple(mutate_inputs)
        self.stateful_infer = bool(stateful_infer)
        self.aux_dtypes = dict(aux_dtypes or {})
        self.slot_state = dict(slot_state or {})
        self.state_reads = state_reads
        self.donate_aux = bool(donate_aux)
        self.shape_passthrough = bool(shape_passthrough)
        self.doc = doc
        # arity check up front (it used to happen lazily at the first
        # symbol shape walk): a malformed infer fn names its op here
        # instead of failing as a confusing TypeError mid-inference
        self._infer_accepts_out = _validate_infer_signature(
            name, "infer_shape", infer_shape)
        _validate_infer_signature(name, "infer_type", infer_type)

    # --- variadic-aware accessors ---------------------------------------
    def input_names(self, attrs=None):
        if callable(self._inputs):
            return list(self._inputs(attrs or {}))
        return list(self._inputs)

    def aux_names(self, attrs=None):
        if callable(self._aux):
            return list(self._aux(attrs or {}))
        return list(self._aux)

    def num_outputs(self, attrs=None):
        if callable(self._num_outputs):
            return self._num_outputs(attrs or {})
        return self._num_outputs

    def num_visible_outputs(self, attrs=None):
        """Outputs exposed to composition (reference: NNVM
        num_visible_outputs — BatchNorm hides mean/var, Dropout its mask)."""
        if self._num_visible is None:
            return self.num_outputs(attrs)
        if callable(self._num_visible):
            return self._num_visible(attrs or {})
        return self._num_visible

    def output_names(self, attrs=None):
        if self._output_names is None:
            n = self.num_outputs(attrs)
            return ["output"] if n == 1 else [f"output{i}" for i in range(n)]
        if callable(self._output_names):
            return list(self._output_names(attrs or {}))
        return list(self._output_names)

    # --- kernel-tier variants + cost metadata ---------------------------
    def add_variant(self, name, forward, eligible=None,
                    kernel_spec=None):
        """Attach an alternative kernel implementation.

        ``forward`` has the full op signature (attrs, inputs, aux,
        is_train, rng) -> (outputs, new_aux); ``eligible(attrs,
        in_shapes, in_dtypes)`` optionally restricts the shapes/attrs
        the kernel handles. ``name="xla"`` is reserved for the stock
        ``self.forward`` composition and cannot be overridden.

        ``kernel_spec`` declares a Pallas kernel's worst-case VMEM
        tiles and numerics-gate dtype coverage
        (``{"tiles": [((rows, cols), dtype), ...], "dtypes": (...)}``);
        it is validated HERE, at registration — an infeasible kernel
        (VMEM-overflowing tile, lane/sublane misalignment, uncoverable
        dtypes) raises MXNetError with its PK9xx rule id at import
        instead of being silently never-selected by the autotuner
        (analysis/kernelcheck.py).
        """
        if name == "xla":
            raise MXNetError(
                f"op {self.name!r}: 'xla' names the stock forward; "
                "register a differently-named variant")
        if kernel_spec is not None:
            from ..analysis.kernelcheck import validate_kernel_spec
            validate_kernel_spec(self.name, name, kernel_spec)
        self.variants[name] = {"fn": forward, "eligible": eligible,
                               "kernel_spec": kernel_spec}
        return self

    def variant_fn(self, name):
        """Forward callable for one tier: 'xla' -> the stock forward."""
        if name == "xla":
            return self.forward
        return self.variants[name]["fn"]

    def variant_eligible(self, name, attrs, in_shapes, in_dtypes):
        if name == "xla":
            return True
        rec = self.variants.get(name)
        if rec is None:
            return False
        if rec["eligible"] is None:
            return True
        try:
            return bool(rec["eligible"](attrs, in_shapes, in_dtypes))
        except Exception:
            return False

    def set_cost(self, flops=None, bytes_moved=None):
        """Attach/replace cost metadata (fn(attrs, in_shapes)->float)."""
        if flops is not None:
            self.flops = flops
        if bytes_moved is not None:
            self.bytes_moved = bytes_moved
        return self

    def has_cost(self):
        return self.flops is not None and self.bytes_moved is not None

    def cost(self, attrs, in_shapes):
        """(flops, bytes) for one forward execution, or None when the op
        has no metadata or the estimate fails (partial shapes)."""
        if not self.has_cost():
            return None
        try:
            return (float(self.flops(attrs, in_shapes)),
                    float(self.bytes_moved(attrs, in_shapes)))
        except Exception:
            return None

    def normalize_attrs(self, kwargs):
        """Parse raw kwargs/JSON strings into the typed attr dict."""
        attrs = {}
        for key, val in kwargs.items():
            if val is None:
                continue
            spec = self.attr_spec.get(key)
            if spec is not None:
                parser = spec[0]
                attrs[key] = parser(val) if parser else val
            else:
                attrs[key] = val
        for key, spec in self.attr_spec.items():
            if key not in attrs and len(spec) > 1 and spec[1] is not None:
                attrs[key] = spec[1]
        return attrs

    def __repr__(self):
        return f"OpDef({self.name})"


def read_counts(*counts):
    """``OpDef.state_reads``' table ``{count: (counter, ring field)}``
    from ``(counter or None, ring field or None)`` pairs: a count goes
    by its counter's name, or by its ring field where it has none."""
    return {counter or field: (counter, field) for counter, field in counts}


def _validate_infer_signature(op_name, what, fn):
    """Registration-time arity check for infer_shape/infer_type.

    Returns whether the fn accepts the optional third ``out_known``
    argument (bidirectional inference), the property symbol.py used to
    probe lazily per call. Raises MXNetError naming the op when the fn
    cannot even accept the mandatory ``(attrs, in_shapes)`` pair.
    """
    if fn is None:
        return False
    if not callable(fn):
        raise MXNetError(
            f"op {op_name!r}: {what} must be callable, got "
            f"{type(fn).__name__}")
    import inspect
    try:
        sig = inspect.signature(fn)
    except (ValueError, TypeError):
        return False          # builtins/partials: cannot introspect
    required = 0
    max_positional = 0
    has_varargs = False
    for p in sig.parameters.values():
        if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
            max_positional += 1
            if p.default is p.empty:
                required += 1
        elif p.kind == p.VAR_POSITIONAL:
            has_varargs = True
        elif p.kind == p.KEYWORD_ONLY and p.default is p.empty:
            raise MXNetError(
                f"op {op_name!r}: {what} has a required keyword-only "
                f"parameter {p.name!r}; inference calls it positionally "
                "as (attrs, in_shapes[, out_known])")
    if not has_varargs and (max_positional < 2 or required > 3):
        raise MXNetError(
            f"op {op_name!r}: {what} must accept (attrs, in_shapes"
            f"[, out_known]), got signature {sig}")
    return has_varargs or max_positional >= 3


def _wrap_simple(fn):
    """Lift fn(attrs, *inputs) -> array|tuple into the full signature."""
    def forward(attrs, inputs, aux, is_train, rng):
        out = fn(attrs, *inputs)
        if isinstance(out, (tuple, list)):
            return list(out), []
        return [out], []
    return forward


def register(name, inputs=("data",), simple=None, full=None, **kw):
    """Register an op. Use as a decorator or direct call.

    ``simple=fn`` registers fn(attrs, *inputs); ``full=fn`` registers the
    5-arg signature. As a decorator, wraps a simple fn unless
    ``full_signature=True`` is passed.
    """
    full_signature = kw.pop("full_signature", False)

    def do_register(fn, is_full):
        forward = fn if is_full else _wrap_simple(fn)
        opdef = OpDef(name, forward, inputs=inputs, **kw)
        if name in OP_REGISTRY:
            raise MXNetError(f"op {name!r} registered twice")
        OP_REGISTRY[name] = opdef
        return fn

    if simple is not None:
        do_register(simple, False)
        return OP_REGISTRY[name]
    if full is not None:
        do_register(full, True)
        return OP_REGISTRY[name]

    def decorator(fn):
        do_register(fn, full_signature)
        return fn

    return decorator


def alias(new_name, existing):
    """Register an alternative public name for an existing op."""
    opdef = get_op(existing)
    if new_name not in OP_REGISTRY:
        OP_REGISTRY[new_name] = opdef
    return opdef


def get_op(name):
    try:
        return OP_REGISTRY[name]
    except KeyError:
        raise MXNetError(f"operator {name!r} is not registered") from None


def list_ops():
    return sorted(OP_REGISTRY)
