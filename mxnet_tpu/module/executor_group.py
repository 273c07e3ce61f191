"""DataParallelExecutorGroup: device-parallel execution of one symbol.

Reference design (reference: python/mxnet/module/executor_group.py, 651 LoC):
slice the batch across devices (``decide_slices``, :207-231), bind one
Executor per context (:537-629), fan out forward/backward, sum gradients via
KVStore.

TPU-native design — the central SPMD decision of this framework: bind ONE
executor whose data arrays are sharded over a first-class named
``jax.sharding.Mesh`` (``parallel/mesh.build_mesh``) and whose params are
placed per a sharding plan. XLA's SPMD partitioner then runs the very
same jitted fwd+bwd program on every chip and inserts the gradient
all-reduce (psum over ICI) automatically — replacing the reference's
per-device executors + KVStore push/pull with compiler-inserted
collectives (SURVEY.md §5.8 "TPU-native equivalent"). Two arrangements:

* default — 1-D ``data`` mesh over the bound contexts, params
  replicated (the shape every kvstore-era test pins);
* ``spmd=True`` (``Module.bind/fit(spmd=True)`` / ``MXNET_SPMD``) — the
  multi-axis mesh from ``MeshConfig``/``MXNET_MESH_*`` with a
  ``parallel/spmd.SpmdPlan``: params sharded per ``placement.py``'s
  ctx_group lowering on the ``model`` axis, optimizer state riding the
  same specs, ZeRO-1 as a spec change on the state leaves, kvstore
  optional.

The class keeps the reference's surface (param_arrays/grad_arrays/
forward/backward/update_metric) so Module and the KVStore update paths
work unchanged: with one logical executor, ``param_arrays`` holds one
entry per param.
"""
from __future__ import annotations

import collections
import logging
import os

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from ..base import MXNetError
from ..ndarray import NDArray, zeros as nd_zeros
from ..io import DataDesc
from .. import program_cache as _progcache
from .. import telemetry as _telemetry
from ..parallel import mesh as _mesh_mod
from ..parallel import zero as _zero_mod
from ..parallel.spmd import SpmdPlan

__all__ = ["DataParallelExecutorGroup"]


def _ssq32(vals):
    """Traced global sum of squares over an iterable of arrays (f32
    accumulator). Shared by the per-step health stats and the
    window-boundary param-stat readings."""
    acc = jnp.zeros((), jnp.float32)
    for v in vals:
        v32 = v.astype(jnp.float32)
        acc = acc + jnp.sum(v32 * v32)
    return acc


def _window_param_stats(health, w_start, w_end, watched):
    """Add the window-level param stats to a health dict (traced).

    param-norm and update-ratio need a full pass over the param set;
    done per step that pass reads the donated/carried buffers and
    defeats XLA's in-place update (measured: an O(params) copy every
    step). Both are therefore computed ONCE per dispatch window — over
    the window's closing params and the window-wide delta — where the
    single amortised read is in the noise. On the K=1 path a window IS
    one step, so the reference per-step semantics are unchanged there;
    on the scan path update_ratio reports the K-step window ratio.
    """
    wsq = _ssq32(w_end[nm] for nm in watched)
    dsq = _ssq32(w_end[nm] - w_start[nm].astype(w_end[nm].dtype)
                 for nm in watched)
    pn = jnp.sqrt(wsq)
    out = dict(health)
    out["param_norm"] = pn
    out["update_ratio"] = jnp.sqrt(dsq) / jnp.maximum(
        pn, jnp.float32(1e-12))
    return out


def _narrow_float(compute_dtype):
    """``compute_dtype`` as a dtype if it is a float type narrower than
    float32, else None (no ``compute_dtype``, float32, the quantized
    tiers)."""
    if compute_dtype is None:
        return None
    try:
        want = jnp.dtype(compute_dtype)
    except TypeError:
        return None
    if not jnp.issubdtype(want, jnp.floating) or want.itemsize >= 4:
        return None
    return want


def compute_width_params(arg_params, compute_dtype):
    """``{name: dtype}`` of the parameters in ``arg_params`` that are
    handed over already at ``compute_dtype``, a float type narrower
    than float32: the rule by which a binding that may train binds a
    parameter's cell at the dtype given instead of as a float32 master
    that every step casts (docs/performance.md, "Mixed precision").
    Empty for float32 parameters, for no ``compute_dtype`` and for the
    quantized tiers."""
    want = _narrow_float(compute_dtype)
    if want is None or not arg_params:
        return {}
    return {name: want for name, arr in arg_params.items()
            if getattr(arr, "dtype", None) is not None
            and jnp.dtype(arr.dtype) == want}


def serving_width_params(symbol, input_names, compute_dtype):
    """``{name: dtype}`` for a binding that only ever serves
    (``for_training=False``: no optimizer, no gradient, nothing that
    needs a master): every parameter of ``symbol`` that the step
    programs would cast to ``compute_dtype`` - ``executor._load_var``'s
    set: not an input, not a loss head's label - binds at it, whatever
    dtype it is handed at, and ``set_params`` casts once as it stores.
    A variable that declares its dtype keeps it (``_bind_exec``).
    Empty where ``compute_width_params`` is: no ``compute_dtype``,
    float32, the quantized tiers."""
    want = _narrow_float(compute_dtype)
    if want is None:
        return {}
    from ..executor import loss_label_names
    skip = set(input_names) | loss_label_names(symbol)
    return {name: want for name in symbol.list_arguments()
            if name not in skip}


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None, compute_dtype=None,
                 spmd=False, mesh_config=None, param_dtypes=None):
        self.symbol = symbol
        self.compute_dtype = compute_dtype
        # parameters to bind at another dtype than float32: those handed
        # over already at the compute width (compute_width_params) or,
        # for a serving binding, all it would cast (serving_width_params)
        self.param_dtypes = dict(param_dtypes or {})
        self.contexts = contexts
        self.workload = workload
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.logger = logger
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.param_names = param_names
        self._zero_plan = None          # set by setup_fused_step
        self._load_counters = _telemetry.metrics.held_counters(
            "io.load_batch.aliased", "io.load_batch.puts")
        self._state_layout = None       # flat-shard state transport

        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()

        data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                       for x in data_shapes]
        if label_shapes is not None:
            label_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                            for x in label_shapes]
        self.data_names = [x.name for x in data_shapes]
        self.label_names = [x.name for x in label_shapes] \
            if label_shapes is not None else []

        # grad_req per arg (reference: executor_group.py:233-268)
        if isinstance(grad_req, str):
            base_req = grad_req
        else:
            base_req = None
        self.grad_req = {}
        for name in self.arg_names:
            if name in self.param_names:
                req = (base_req or (grad_req.get(name, "null")
                                    if isinstance(grad_req, dict) else "write"))
                if not for_training or name in self.fixed_param_names:
                    req = "null"
            elif name in self.data_names:
                req = (base_req or "write") if inputs_need_grad else "null"
                if not for_training:
                    req = "null"
            else:
                req = "null"
            self.grad_req[name] = req

        # ---- mesh construction over the bound contexts -------------------
        # both arrangements go through parallel/mesh.build_mesh — ONE
        # first-class named mesh per binding (the 1-D ad-hoc Mesh this
        # class used to build inline is the degenerate data-only case)
        devices = [c.jax_device() for c in contexts]
        self._n_dev = len(devices)
        if self._n_dev > 1 and len(set(devices)) != self._n_dev:
            raise MXNetError(
                f"contexts {contexts} resolve to only {len(set(devices))} "
                f"distinct devices ({sorted(set(str(d) for d in devices))}). "
                "On a CPU host set XLA_FLAGS="
                "--xla_force_host_platform_device_count=N to get N virtual "
                "devices.")
        self._spmd_plan = None
        if spmd:
            # param specs are derived at bind time (shapes needed);
            # zero is enabled at optimizer-arming time
            self._spmd_plan = SpmdPlan(
                SpmdPlan.build_mesh_for(devices, mesh_config))
            self._mesh = self._spmd_plan.mesh
            self._data_sharding = self._spmd_plan.data_sharding()
            self._repl_sharding = self._spmd_plan.replicated
            self._stacked_sharding = self._spmd_plan.data_sharding(
                stacked=True)
            self._n_data = self._spmd_plan.n_data_shards()
        elif self._n_dev > 1:
            self._mesh = _mesh_mod.build_mesh(devices=devices)
            self._data_sharding = NamedSharding(self._mesh, P("data"))
            self._repl_sharding = NamedSharding(self._mesh, P())
            # K-stacked batches: axis 0 is the scan step, batch is axis 1
            self._stacked_sharding = NamedSharding(self._mesh,
                                                   P(None, "data"))
            self._n_data = self._n_dev
        else:
            self._mesh = None
            # what `_place` on the one device gives; read by
            # `_lies_as_data` alone, the puts name the device
            self._data_sharding = SingleDeviceSharding(devices[0])
            self._repl_sharding = None
            self._stacked_sharding = None
            self._n_data = 1

        self.batch_size = data_shapes[0].shape[
            DataDesc.get_batch_axis(data_shapes[0].layout)]
        if self._n_data > 1 and self.batch_size % self._n_data != 0:
            raise MXNetError(
                f"batch size {self.batch_size} must be divisible by the "
                f"data-axis size {self._n_data}")

        self.data_shapes = data_shapes
        self.label_shapes = label_shapes

        self._bind_exec(shared_group)

    # ------------------------------------------------------------------ bind
    def _place(self, arr, kind, name=None):
        """Device-place a jnp array: batch-sharded, per-plan param
        sharding (SPMD mode), or replicated."""
        if self._mesh is None:
            return jax.device_put(arr, self.contexts[0].jax_device())
        if kind == "data":
            if self._spmd_plan is not None:
                # shape-aware spec: P(data, seq) on (batch, sequence)
                # when the plan carries a nonempty seq axis (the
                # long-context layout ring attention consumes)
                sharding = self._spmd_plan.data_sharding_for(arr.shape)
            else:
                sharding = self._data_sharding
        elif self._spmd_plan is not None and name is not None:
            sharding = self._spmd_plan.param_sharding(name)
        else:
            sharding = self._repl_sharding
        return jax.device_put(arr, sharding)

    def _bind_exec(self, shared_group):
        from ..executor import Executor
        shapes = {d.name: d.shape for d in self.data_shapes}
        if self.label_shapes is not None:
            shapes.update({l.name: l.shape for l in self.label_shapes})
        arg_shapes, out_shapes, aux_shapes = \
            self.symbol.infer_shape(**shapes)
        arg_types = {d.name: d.dtype for d in self.data_shapes}
        # params declared with an explicit var dtype bind a cell of that
        # dtype (the int8 tier's quantized weights — set_params would
        # otherwise silently upcast them into a float32 cell, wasting
        # the HBM the quantization bought); analysis rule GV105 audits
        # the same declaration
        for n in self.symbol._topo_nodes():
            if n.is_variable and n._extra.get("__dtype__") and \
                    n.name not in arg_types:
                arg_types[n.name] = np.dtype(n._extra["__dtype__"])
        for name, dtype in self.param_dtypes.items():
            arg_types.setdefault(name, np.dtype(dtype))

        if self._spmd_plan is not None:
            # lower ctx_group tags onto the model axis now that shapes
            # are known (re-derived on reshape: divisibility may change)
            self._spmd_plan.derive_param_specs(
                self.symbol, dict(zip(self.arg_names, arg_shapes)))

        shared_params = {}
        if shared_group is not None:
            shared_params = dict(zip(shared_group.arg_names,
                                     shared_group.executor.arg_arrays))

        args = {}
        grads = {}
        # cells reused from a shared_group: the donation/aliasing
        # analysis pass (analysis rule DA202) flags these if a fused
        # (donating) plan ever arms over them
        self._shared_param_names = set()
        for name, shape in zip(self.arg_names, arg_shapes):
            kind = "data" if (name in self.data_names or
                              name in self.label_names) else "param"
            if name in shared_params and kind == "param":
                args[name] = shared_params[name]  # shared NDArray cell
                self._shared_param_names.add(name)
            else:
                dtype = arg_types.get(name, np.float32)
                args[name] = NDArray(self._place(
                    jnp.zeros(shape, dtype=np.dtype(dtype)
                              if dtype != np.float64 else np.float32),
                    kind, name))
            if self.grad_req.get(name, "null") != "null":
                grads[name] = NDArray(self._place(
                    jnp.zeros(shape, dtype=np.float32), kind, name))
        aux = {}
        shared_aux = {}
        if shared_group is not None:
            shared_aux = dict(zip(shared_group.aux_names,
                                  shared_group.executor.aux_arrays))
        # aux cells honor a declared dtype (attention_decode's int32
        # cache cursor, fp8 cache storage). The undeclared aux of a
        # stateful inference op (the KV caches) bind at the compute
        # width: the program reads them cast to it and writes them back
        # at it, so an f32 cell would change dtype after the first
        # forward and every decode program would compile twice.
        aux_types = {n.name: np.dtype(n._extra["__dtype__"])
                     for n in self.symbol._topo_nodes()
                     if n.is_variable and n._extra.get("__is_aux__")
                     and n._extra.get("__dtype__")}
        if self.compute_dtype is not None:
            for n in self.symbol._topo_nodes():
                if n.is_variable or not n.opdef().stateful_infer:
                    continue
                n_aux = len(n.opdef().aux_names(n.attrs))
                for inp, _ in n.inputs[len(n.inputs) - n_aux:]:
                    aux_types.setdefault(inp.name,
                                         np.dtype(self.compute_dtype))
        for name, shape in zip(self.aux_names, aux_shapes):
            want = np.dtype(aux_types.get(name, np.float32))
            cell = shared_aux.get(name)
            # share an aux cell only when shape AND dtype agree: a
            # slot-pooled decode ladder binds the SAME aux names at a
            # different slot count per rung (the KV cache pool scales
            # with the bucket key) — aliasing the leader's cell there
            # would hand every rung a wrongly-shaped cache
            if cell is not None and tuple(cell.shape) == tuple(shape) \
                    and np.dtype(str(cell.asjax().dtype)) == want:
                aux[name] = cell
            else:
                aux[name] = NDArray(
                    self._place(jnp.zeros(shape, dtype=want),
                                "param", name))

        # device-topology token for the program-cache keys: a compiled
        # program bakes its mesh's collective structure in, so a mesh
        # change (1→8 devices, axis reshape, different spec set) must
        # never reuse a stale program
        if self._spmd_plan is not None:
            mesh_token = self._spmd_plan.cache_token()
        elif self._mesh is not None:
            mesh_token = _mesh_mod.mesh_token(self._mesh)
        else:
            mesh_token = None           # Executor derives a device token
        self.executor = Executor(self.symbol, self.contexts[0], args, grads,
                                 self.grad_req, aux,
                                 compute_dtype=self.compute_dtype,
                                 mesh_token=mesh_token,
                                 spmd_plan=self._spmd_plan,
                                 n_devices=self._n_dev)
        self.execs = [self.executor]  # reference-compat alias

        # flat layout — one logical sharded executor, so one array per
        # param (the reference's per-device inner lists don't exist here);
        # grad entry is None for fixed/untrained params, keeping 1:1 zip
        self.param_arrays = [self.executor.arg_dict[name]
                             for name in self.param_names]
        self.grad_arrays = [self.executor.grad_dict.get(name)
                            for name in self.param_names]
        self.aux_arrays = list(self.executor.aux_arrays)

        self.data_arrays = [self.executor.arg_dict[name]
                            for name in self.data_names]
        self.label_arrays = [self.executor.arg_dict[name]
                             for name in self.label_names
                             if name in self.executor.arg_dict]

    # ------------------------------------------------------- fused training
    def setup_fused_step(self, optimizer, zero_stage=0, remat=None):
        """Compile forward+backward+optimizer-update into ONE jitted XLA
        program (the TPU-native analog of the reference's bulk train
        segment, graph_executor.cc:678-756, plus its fused update ops).

        ``zero_stage=1`` selects the in-program reduce-scatter comm plan
        (parallel/zero.py) on a multi-device mesh: gradients arrive
        shard-wise, the update runs on 1/N flat shards with sharded
        optimizer state, and the new params all-gather back — otherwise
        the replicated (all-reduce) plan runs unchanged.

        ``remat`` (default ``MXNET_REMAT_POLICY``, else ``none``)
        applies a rematerialization policy to the step's differentiated
        forward — ``dots`` keeps matmul/conv outputs saved and
        recomputes the elementwise chains between them, ``all`` replays
        the whole forward inside the backward — and additionally
        donates the step's eval-only intermediates (the rng key chain
        and, when the training forward refreshes every aux entry, the
        aux buffers). The policy is part of the program-cache key and
        of the kernel-tier autotune key (mxnet_tpu/remat.py).

        Per-batch work then becomes: slice batch -> async device_put ->
        one XLA dispatch -> buffer swaps. Returns False when the
        optimizer or binding can't express it (imperative path remains).
        """
        from ..executor import naive_engine_active
        from .. import remat as _remat
        self._zero_plan = None
        self._state_layout = None
        self._remat_policy = _remat.resolve(remat)
        plan = optimizer.fused_plan()
        if plan is None or not self.for_training or self.inputs_need_grad:
            return False
        if naive_engine_active():
            # NaiveEngine debug mode: keep the imperative per-phase path so
            # every op replays serially through the un-jitted runner
            return False
        if any(self.grad_req.get(nm) not in ("write", "null")
               for nm in self.arg_names):
            return False
        init_state, update = plan
        exe = self.executor
        watched = [nm for nm in self.param_names
                   if self.grad_req.get(nm) == "write"]
        if not watched:
            return False

        # comm plan: in-program reduce-scatter + sharded update (ZeRO-1)
        # needs a data mesh and an elementwise update; anything else
        # keeps the replicated all-reduce plan. Under the SPMD plan,
        # ZeRO-1 is purely a spec change: state_spec flips to P('data')
        # over the flat layout and the step applies it via
        # zero.apply_spec_update — no plan object threaded through.
        spmd_plan = self._spmd_plan
        can_shard = (self._mesh is not None and
                     (spmd_plan.can_zero() if spmd_plan is not None
                      else self._n_data > 1))
        if (zero_stage and can_shard
                and getattr(optimizer, "fused_update_elementwise", False)):
            if spmd_plan is not None:
                spmd_plan.enable_zero()
                self._state_layout = spmd_plan.state_layout
            else:
                from ..parallel.zero import ZeroPlan
                self._zero_plan = ZeroPlan(self._mesh, "data")
                self._state_layout = self._zero_plan
        elif zero_stage:
            self.logger.info(
                "zero_stage=%s requested but unavailable (data shards=%s, "
                "elementwise=%s); using the replicated update plan",
                zero_stage, self._n_data,
                getattr(optimizer, "fused_update_elementwise", False))
        zero_plan = self._zero_plan

        runner = exe._runner
        loss_mask = exe._loss_mask
        # (output index, label name) pairs, positional like
        # Accuracy.update's zip(labels, preds) — names missing from the
        # executor keep their index so pairings never shift
        metric_pairs = [(i, nm) for i, nm in enumerate(self.label_names)
                        if nm in exe.arg_dict]
        self._fused_metric_pairs = metric_pairs

        # Gradients as program OUTPUTS cost ~5% of the step (measured on
        # v5e: 161 extra materializations the fuser must keep live past
        # the update instead of folding into it). The default fit loop
        # never reads them, so they're off unless requested; the staged
        # (non-fused) path always populates grad_dict.
        keep_grads = os.environ.get("MXNET_FUSED_KEEP_GRADS", "0") == "1"
        if not keep_grads:
            # the fused program will never write these buffers — poison
            # them once so a stale read returns NaN loudly instead of
            # plausible pre-step values (set MXNET_FUSED_KEEP_GRADS=1 for
            # live gradients, or install a monitor for the staged path)
            gd = exe.grad_dict
            for nm in watched:
                dst = gd.get(nm)
                if dst is not None and \
                        np.issubdtype(dst.dtype, np.floating):
                    dst._set(jnp.full(dst.shape, jnp.nan,
                                      dst.asjax().dtype))

        remat_policy = self._remat_policy

        # training-health plane (telemetry/health.py): when armed, the
        # program computes a small fixed stat set INSIDE the jitted
        # step — per-step grad global L2 norm, per-loss-head loss and
        # non-finite flag (returned as extra stacked ys), plus one
        # window-level param-norm / update-ratio reading (see
        # _window_param_stats) — all read by the host at window
        # boundaries where it already syncs. Read-only over values the
        # step computes anyway, so armed training is bit-identical to
        # unarmed; arming keys the program cache below.
        health_armed = _telemetry.health.armed()

        # lr/wd arrive as TWO stacked f32 arrays, not 2x161 python
        # scalars: scalar jit args each become their own host->device
        # transfer per dispatch — hundreds of tiny transfers per step
        def step(w, rest, aux_vals, key, states, lr_arr, wd_arr):
            # rng chain lives ON DEVICE: split here (traced) and return
            # the successor key, so per-step randomness costs zero extra
            # host round-trips (next_key() per step was a device dispatch
            # + transfer)
            key, rng = jax.random.split(key)

            def f(wv):
                return runner({**rest, **wv}, aux_vals, True, rng)

            # remat policy: shrink the saved-residual set of this vjp
            # (identity under "none" — the traced program is unchanged)
            f = _remat.wrap(f, remat_policy)

            outs, vjp_fn, new_aux = jax.vjp(f, w, has_aux=True)
            heads = [jnp.ones(o.shape, o.dtype) if is_loss
                     else jnp.zeros(o.shape, o.dtype)
                     for o, is_loss in zip(outs, loss_mask)]
            (grads,) = vjp_fn(heads)
            new_w, new_states = {}, {}
            # the optimizer's part of the program, under a scope of its
            # own: a named scope exists at trace time only, and
            # ``profiler.operator_table`` reads the phase ``update``
            # from it
            with jax.named_scope("update"):
                for i, nm in enumerate(watched):
                    g = grads[nm].astype(w[nm].dtype)
                    if spmd_plan is not None:
                        # spec-driven: the plan's PartitionSpecs pin the
                        # gradient (the psum/reduce-scatter XLA emits), the
                        # update layout, and the new weights (donation needs
                        # input sharding == output sharding)
                        if spmd_plan.zero:
                            nw, ns = _zero_mod.apply_spec_update(
                                update, w[nm], g, states[nm],
                                lr_arr[i], wd_arr[i], spmd_plan.mesh,
                                spmd_plan.state_spec(nm),
                                out_spec=spmd_plan.param_spec(nm))
                        else:
                            p_sh = spmd_plan.param_sharding(nm)
                            g = jax.lax.with_sharding_constraint(g, p_sh)
                            nw, ns = update(w[nm], g, states[nm],
                                            lr_arr[i], wd_arr[i])
                            nw = jax.lax.with_sharding_constraint(nw, p_sh)
                            ns = jax.tree.map(
                                lambda x: jax.lax.with_sharding_constraint(
                                    x, p_sh) if x.shape == nw.shape else x,
                                ns)
                    elif zero_plan is None:
                        nw, ns = update(w[nm], g, states[nm],
                                        lr_arr[i], wd_arr[i])
                    else:
                        nw, ns = zero_plan.apply(update, w[nm], g,
                                                 states[nm],
                                                 lr_arr[i], wd_arr[i])
                    new_w[nm] = nw
                    new_states[nm] = ns
            # top-1 correct counts per (label, output) pair, computed
            # inside the program: the Accuracy metric then costs zero
            # extra dispatches per batch (its own device-side argmax
            # was one more dispatch and round trip)
            mets = []
            with jax.named_scope("metric"):
                for i, nm in metric_pairs:
                    if i >= len(outs):
                        break
                    o, lab = outs[i], rest[nm]
                    if o.ndim > 1 and o.shape != lab.shape:
                        # classification semantics only: prediction
                        # classes must align 1:1 with label elements
                        # after argmax (detection-style structured
                        # labels skip the in-step count and take the
                        # general metric path)
                        if int(np.prod(o.shape[:-1])) != lab.size:
                            break
                        p = jnp.argmax(o, axis=-1)
                    elif o.shape == lab.shape:
                        p = o
                    else:
                        break
                    l = lab.astype(jnp.int32).ravel()
                    mets.append(jnp.sum(p.astype(jnp.int32).ravel() == l))
            health = None
            if health_armed:
                f32 = jnp.float32
                # per-step stats ONLY cover values this step already
                # materialises (grads, outputs): reductions over the
                # param set are NOT free here — params ride the donated
                # scan carry, and any extra reader defeats the in-place
                # update (measured: an O(params) copy per step, +15% on
                # a 1M-param epoch). param-norm / update-ratio are
                # computed once per dispatch window by the program
                # wrappers below instead.
                gsq = _ssq32(grads[nm] for nm in watched)
                # per-loss-head loss value: cross-entropy against the
                # paired label for classification heads, squared error
                # for same-shape heads, mean output for heads that ARE
                # the loss (MakeLoss-style) — mirrors the mets pairing
                label_for = dict((i, nm) for i, nm in metric_pairs)
                losses = []
                for i, (o, is_loss) in enumerate(zip(outs, loss_mask)):
                    if not is_loss:
                        continue
                    nm = label_for.get(i)
                    lab = rest.get(nm) if nm is not None else None
                    o32 = o.astype(f32)
                    if lab is not None and o.ndim > 1 and \
                            o.shape != lab.shape and \
                            int(np.prod(o.shape[:-1])) == lab.size:
                        p = o32.reshape((-1, o.shape[-1]))
                        idx = lab.astype(jnp.int32).reshape((-1, 1))
                        picked = jnp.take_along_axis(p, idx, axis=1)
                        losses.append(-jnp.mean(jnp.log(
                            jnp.maximum(picked, 1e-30))))
                    elif lab is not None and o.shape == lab.shape:
                        d = o32 - lab.astype(f32)
                        losses.append(jnp.mean(d * d))
                    else:
                        losses.append(jnp.mean(o32))
                loss_vec = jnp.stack(losses) if losses \
                    else jnp.zeros((0,), f32)
                finite = (jnp.isfinite(gsq)
                          & jnp.all(jnp.isfinite(loss_vec)))
                # raw scalars, NOT packed into one vector: a pack op
                # (stack/concatenate) is measurably slower in-program
                # than returning the scalars as-is on micro-steps
                health = {
                    "grad_norm": jnp.sqrt(gsq),
                    "loss": loss_vec,
                    "nonfinite": 1.0 - finite.astype(f32),
                }
            return (outs, new_aux, new_w, new_states,
                    grads if keep_grads else None, key, mets, health)

        # donate the watched params and optimizer states: both are
        # replaced by same-shaped outputs every step, so XLA updates them
        # in place instead of allocating fresh buffers. They get their own
        # arguments precisely so donation is safe — `rest` still carries
        # data/label entries that _load_batch can alias to iterator
        # arrays, and donating those would delete the caller's buffers
        # out from under it (measured: "Array has been deleted" in eval
        # paths sharing those arrays). Aux (BN stats) stays undonated by
        # default for the same reason: eval paths read the same cells
        # mid-epoch. A remat policy extends the donation set to the
        # step's eval-only intermediates — the rng key chain, and the
        # aux buffers when the training forward provably refreshes EVERY
        # aux entry (cells then re-point at the returned buffers before
        # any reader runs; an aux entry the step passes through untouched
        # would leave a deleted buffer behind, so partial coverage keeps
        # aux undonated).
        donate = (0, 4)
        if remat_policy != "none":
            donate = (0, 3, 4)
            if self._aux_fully_refreshed():
                donate = (0, 2, 3, 4)
        self._fused_donate = donate
        self._step_core = step      # pure; the scan program re-uses it
        self._fused_keep_grads = keep_grads
        # the comm-plan token keys the traced collective structure:
        # replicated all-reduce vs reduce-scatter/shard-update/all-gather
        # trace differently even for identical symbols and optimizers;
        # the remat token keys the checkpoint-policy + donation shape
        zero_armed = zero_plan is not None or \
            (spmd_plan is not None and spmd_plan.zero)
        self._fused_cache_key = exe.program_cache_key(
            "fused_step", tuple(watched), tuple(metric_pairs), keep_grads,
            optimizer.fused_plan_token(),
            ("comm", "rs" if zero_armed else "ar"),
            ("remat", remat_policy),
            ("health", health_armed))
        self._fused_prog = None
        if self._fused_cache_key is not None:
            self._fused_prog = _progcache.get(self._fused_cache_key)
        if health_armed:
            # single-step program: every step is its own dispatch
            # window, so the window-level param stats land here too
            def fused_one(w, rest, aux_vals, key, states, lr_arr,
                          wd_arr):
                (outs, new_aux, new_w, new_states, grads, key, mets,
                 health) = step(w, rest, aux_vals, key, states,
                                lr_arr, wd_arr)
                health = _window_param_stats(health, w, new_w, watched)
                return (outs, new_aux, new_w, new_states, grads, key,
                        mets, health)
            prog_fn = fused_one
        else:
            prog_fn = step
        if self._fused_prog is not None:
            if _telemetry.enabled():
                _telemetry.counter("executor.jit_cache.hit").inc()
        else:
            if _telemetry.enabled():
                _telemetry.counter("executor.jit_cache.miss").inc()
            prog_fn.__name__ = exe.program_name("fused_step")
            self._fused_prog = _telemetry.wrap_dispatch(
                jax.jit(prog_fn, donate_argnums=donate), "fused_step")
            if self._fused_cache_key is not None:
                _progcache.put(self._fused_cache_key, self._fused_prog)
        _telemetry.optable.register_program(
            exe.program_name("fused_step"), self, "fused_step")
        self._scan_prog = None      # K-step lax.scan program (lazy)
        self._scan_K = 0
        self._scan_failed = False
        self._attr_prev = None      # armed step attribution: a result of
                                    # the dispatch before, waited on next
        self._scan_results = collections.deque()
        self._scan_lrwd = (None, None, None)
        self._fused_watched = watched
        from .. import random as _random
        self._fused_key = self._draw_fused_key()
        self._fused_rng_gen = _random.generation()
        self._fused_lrwd = (None, None, None)  # (key, lr_arr, wd_arr)
        self._fused_metric_scalars = None
        self._last_health = None    # just-dispatched device stat vector
        self._health_queue = collections.deque()   # awaiting readiness
        self._health_armed = health_armed      # drained by take_health()
        # the watched cells must own their buffers exclusively before the
        # first donated step: init_params aliases the same arrays into
        # Module._arg_params, and donating a shared buffer would delete it
        # out from under that holder
        ad = exe.arg_dict
        for nm in watched:
            ad[nm]._set(jnp.array(ad[nm].asjax(), copy=True))
        self._fused_states = {}
        for nm in watched:
            w = exe.arg_dict[nm].asjax()
            if self._state_layout is not None:
                # ZeRO-1 (either plan): created directly in the
                # (n, chunk) sharded layout — each device holds only
                # its 1/N state slice
                self._fused_states[nm] = self._state_layout.init_state(
                    init_state, w)
            else:
                # param-shaped state rides the param's own sharding
                # (replicated, or the SPMD plan's model-axis spec);
                # differently-shaped leaves replicate
                def _put(x, _w=w):
                    if self._mesh is None or \
                            getattr(x, "shape", ()) == _w.shape:
                        return jax.device_put(x, _w.sharding)
                    return jax.device_put(x, self._repl_sharding)
                self._fused_states[nm] = jax.tree.map(_put, init_state(w))
        return True

    def _draw_fused_key(self):
        """A fresh rng key for the fused step, device-chained thereafter.
        Committed to the binding's device(s) like the key every later
        step gets back: an uncommitted first key gives the first call
        other input shardings than the second, and XLA compiles the
        whole step twice."""
        from .. import random as _random
        return self._place(_random.next_key(), "param")

    def _aux_fully_refreshed(self):
        """Does one training forward return a new value for EVERY aux
        entry? (True for the BatchNorm moving-stat contract — and the
        empty-aux case.) Gates aux donation under a remat policy: a
        pass-through aux entry would otherwise be left as a deleted
        buffer in its cell. Pure trace (``jax.eval_shape``)."""
        import jax as _jax
        exe = self.executor
        if not exe.aux_names:
            return True
        try:
            _outs, new_aux = _jax.eval_shape(
                lambda a, x, r: exe._runner(a, x, True, r),
                exe._arg_vals(), exe._aux_vals(),
                _jax.random.PRNGKey(0))
            return set(new_aux) == set(exe.aux_names)
        except Exception:
            return False

    def lower_fused_step(self):
        """``jax.stages.Lowered`` of the armed fused step at the bound
        buffers' shapes and shardings — for cost and memory analysis and
        for the compiled HLO text. lr/wd enter the program as arrays, so
        zeros stand in for them."""
        exe = self.executor
        rest = exe._arg_vals()
        w = {nm: rest.pop(nm) for nm in self._fused_watched}
        hyper = jnp.zeros((len(self._fused_watched),), jnp.float32)
        return self._fused_prog.lower(w, rest, exe._aux_vals(),
                                      self._fused_key, self._fused_states,
                                      hyper, hyper)

    def lower_scan_step(self):
        """``jax.stages.Lowered`` of the armed K-step scan program: the
        window's stacked inputs enter as shapes with the placement
        ``_place_stacked`` gives them, nothing is allocated."""
        exe = self.executor
        K = self._scan_K
        arg_vals = exe._arg_vals()
        w = {nm: arg_vals.pop(nm) for nm in self._fused_watched}
        xs_in = {}
        for nm in list(self.data_names) + list(self.label_names):
            if nm in arg_vals:
                cell = arg_vals.pop(nm)
                shape = (K,) + tuple(cell.shape)
                xs_in[nm] = jax.ShapeDtypeStruct(
                    shape, cell.dtype,
                    sharding=self._stacked_placement(shape))
        hyper = jnp.zeros((K, len(self._fused_watched)), jnp.float32)
        return self._scan_prog.lower(
            w, self._fused_states, self._fused_key, exe._aux_vals(),
            arg_vals, {"in": xs_in, "lr": hyper, "wd": hyper})

    def lower_program(self, kind):
        """The registry's way back to a step program
        (``telemetry/optable.py``): ``fused_step`` or ``scan_step``."""
        if kind == "scan_step":
            return self.lower_scan_step()
        return self.lower_fused_step()

    def fused_memory_report(self):
        """Byte accounting of the armed fused step under the active
        remat policy: the VJP residual set (the activations stored
        between the forward and backward halves — what a remat policy
        shrinks), plus the param/batch footprints for headroom math.
        Trace-only (``remat.residual_bytes``); returns None when the
        fused step is not armed. Mirrored into ``memory.fused.*`` gauges
        for diagnose/bench consumption."""
        import jax as _jax
        from .. import remat as _remat
        if getattr(self, "_step_core", None) is None:
            return None
        exe = self.executor

        def nbytes(tree):
            return int(sum(
                int(np.prod(v.shape)) * v.dtype.itemsize
                for v in _jax.tree_util.tree_leaves(tree)))

        arg_vals = exe._arg_vals()
        w = {nm: arg_vals.pop(nm) for nm in self._fused_watched}
        aux_vals = exe._aux_vals()
        rng = _jax.random.PRNGKey(0)
        runner = exe._runner

        def f(wv):
            return runner({**arg_vals, **wv}, aux_vals, True, rng)

        policy = getattr(self, "_remat_policy", "none")
        try:
            resid = _remat.residual_bytes(_remat.wrap(f, policy), w)
        except Exception:
            return None
        batch_names = set(self.data_names) | set(self.label_names)
        report = {
            "policy": policy,
            "residual_bytes": resid,
            "param_bytes": nbytes(w),
            "state_bytes": nbytes(self._fused_states),
            "batch_bytes": nbytes([v for nm, v in arg_vals.items()
                                   if nm in batch_names]),
            "batch_size": self.batch_size,
            "donated_args": list(getattr(self, "_fused_donate", (0, 4))),
        }
        for k in ("residual_bytes", "param_bytes", "state_bytes",
                  "batch_bytes"):
            _telemetry.gauge(f"memory.fused.{k}",
                             policy=policy).set(report[k])
        _telemetry.flightrec.note("memory.fused_step", **{
            k: report[k] for k in ("policy", "residual_bytes",
                                   "param_bytes", "batch_bytes")})
        return report

    def static_memory_plan(self, policy=None, buckets=None,
                           capacity_bytes=None):
        """Static peak-HBM plan for this binding — the zero-trace fast
        path of the batch-bucket headroom gate.

        Same component semantics as ``fused_memory_report`` (the tests
        cross-check the two within 5%) but computed purely from the
        graph by ``analysis.memplan``: no ``eval_shape``, no trace, no
        armed optimizer required. When the fused step IS armed, the
        exact state-tree bytes and remat policy are used; otherwise the
        planner's optimizer-multiplier estimate. Returns the plan dict
        (plus ``headroom_bucket`` when ``buckets``+``capacity_bytes``
        are given), mirrored into the ``memplan.*`` gauges.
        """
        from .. import remat as _remat
        from ..analysis import memplan as _memplan
        shapes = {d.name: tuple(d.shape) for d in self.data_shapes}
        for l in (self.label_shapes or []):
            shapes[l.name] = tuple(l.shape)
        state_bytes = None
        states = getattr(self, "_fused_states", None)
        if states:
            # exact armed-state bytes (the flat ZeRO tree is the full
            # (n, chunk) layout — global, like the estimate; the
            # planner divides per device when zero=True)
            state_bytes = int(sum(
                int(np.prod(v.shape)) * v.dtype.itemsize
                for v in jax.tree_util.tree_leaves(states)))
        policy = policy or getattr(self, "_remat_policy", None) \
            or _remat.active()
        plan = _memplan.plan_symbol(
            self.symbol, shapes, policy=policy,
            for_training=self.for_training,
            compute_dtype=self.compute_dtype,
            n_data=self._n_data, spmd_plan=self._spmd_plan,
            zero=bool(self._state_layout is not None
                      or (self._spmd_plan is not None
                          and self._spmd_plan.zero)),
            donation=getattr(self, "_fused_prog", None) is not None,
            fixed_params=self.fixed_param_names,
            state_bytes=state_bytes)
        _memplan.record_plan(plan)
        if buckets and capacity_bytes and plan.get("per_sample_bytes"):
            from ..telemetry.memory import batch_headroom
            plan["headroom_bucket"] = batch_headroom(
                capacity_bytes, plan["fixed_bytes"] + plan["grad_bytes"],
                plan["per_sample_bytes"], buckets)
        return plan

    # ----------------------------------------------- fused-state transport
    def export_fused_states(self):
        """Host-format (param-shaped numpy) fused optimizer states — the
        checkpoint representation, identical for the replicated and the
        ZeRO-sharded layouts (either plan) so checkpoints move between
        arrangements."""
        if self._state_layout is None:
            return jax.tree.map(np.asarray, self._fused_states)
        return {nm: self._state_layout.export_state(
                    st, self.executor.arg_dict[nm].shape)
                for nm, st in self._fused_states.items()}

    def import_fused_states(self, states_host):
        """Load host-format states back into the armed plan's layout."""
        if self._state_layout is None:
            self._fused_states = jax.tree.map(
                lambda old, new: jax.device_put(np.asarray(new),
                                                old.sharding),
                self._fused_states, states_host)
            return
        self._fused_states = {
            nm: (self._state_layout.import_state(states_host[nm])
                 if nm in states_host else st)
            for nm, st in self._fused_states.items()}

    def import_staged_state(self, nm, staged):
        """Project one param's staged (param-shaped, possibly nested)
        optimizer state onto the fused device layout."""
        layout = self._state_layout

        def walk(old, new):
            if isinstance(old, (tuple, list)):
                return type(old)(walk(o, n) for o, n in zip(old, new))
            arr = new.asnumpy() if isinstance(new, NDArray) \
                else np.asarray(new)
            if layout is not None:
                return jax.device_put(layout._flat(jnp.asarray(arr)),
                                      layout.sharded)
            return jax.device_put(arr, old.sharding)

        self._fused_states[nm] = walk(self._fused_states[nm], staged)

    def defused_states(self):
        """Device-side fused states in param shape, for migrating into
        the staged updater (Module._defuse)."""
        if self._state_layout is None:
            return dict(self._fused_states)
        return {nm: self._state_layout.device_state_to_param_shape(
                    st, self.executor.arg_dict[nm].shape)
                for nm, st in self._fused_states.items()}

    # ------------------------------------------------------- rng transport
    def rng_chain(self):
        """Host copy of the device-chained rng key (None when the fused
        path never armed). Part of the exact-resume state: the dropout
        stream of step N+1 is a pure function of this key."""
        key = getattr(self, "_fused_key", None)
        return None if key is None else np.asarray(key)

    def set_rng_chain(self, key):
        """Reinstate a checkpointed device rng chain and re-tag the
        generation so the restored chain is not immediately re-drawn."""
        from .. import random as _random
        self._fused_key = self._place(jnp.asarray(np.asarray(key)), "param")
        self._fused_rng_gen = _random.generation()

    def _wait_for_step_before(self, result):
        """Armed step attribution's ``device`` wait: block until the
        dispatch BEFORE the one just made has finished, and keep
        ``result`` - an in-step metric scalar or an output of the new
        dispatch, which no later step takes over as the donated
        parameters and states are - to wait on next time. All of a
        program's results are ready when it ends and step n cannot end
        before step n-1, so the wait is device time on the host's
        clock, completion to completion, and the chip always has the
        next step queued, as in a run nobody measures. The first armed
        step waits on nothing."""
        prev, self._attr_prev = self._attr_prev, result
        if prev is not None:
            jax.block_until_ready(prev)

    def fused_step(self, data_batch, lrs, wds):
        """Run one fused train step; swap new params/state/outputs in
        (gradients are emitted and written back only under
        ``MXNET_FUSED_KEEP_GRADS=1`` — they cost ~5% of the step).

        Step attribution (telemetry/stepattr.py, armed fit loops only):
        host batch staging counts as ``assemble``, the async program
        call as ``dispatch``, and the wait for the step BEFORE this one
        to finish (``_wait_for_step_before``) as ``device``: the loop
        stays one dispatch ahead of the chip, as it is unarmed."""
        from .. import random as _random
        _sa = _telemetry.stepattr
        sa_on = _sa.active()
        if sa_on:
            sa_t0 = _sa.clock()
        exe = self.executor
        self._load_batch(data_batch)
        if self._fused_rng_gen != _random.generation():
            # mx.random.seed() was called since the last step: re-draw
            # the device chain from the reseeded host chain so seeding
            # stays effective mid-training (reference seed semantics)
            self._fused_key = self._draw_fused_key()
            self._fused_rng_gen = _random.generation()

        arg_vals = exe._arg_vals()
        w = {nm: arg_vals.pop(nm) for nm in self._fused_watched}
        # lr/wd device arrays are cached by value: with a fixed schedule
        # this is zero host->device transfers per step (two per step
        # otherwise)
        lrwd_key = (tuple(lrs[nm] for nm in self._fused_watched),
                    tuple(wds[nm] for nm in self._fused_watched))
        if self._fused_lrwd[0] != lrwd_key:
            self._fused_lrwd = (
                lrwd_key, jnp.asarray(lrwd_key[0], jnp.float32),
                jnp.asarray(lrwd_key[1], jnp.float32))
        _, lr_arr, wd_arr = self._fused_lrwd
        if sa_on:
            sa_t1 = _sa.clock()
            _sa.note("assemble", sa_t1 - sa_t0)
        (outs, new_aux, new_w, new_states, grads, self._fused_key,
         mets, health) = self._fused_prog(w, arg_vals, exe._aux_vals(),
                                          self._fused_key,
                                          self._fused_states,
                                          lr_arr, wd_arr)
        self._last_health = health        # device scalars (or None)
        if sa_on:
            sa_t2 = _sa.clock()
            _sa.note("dispatch", sa_t2 - sa_t1)
            self._wait_for_step_before(mets[0] if mets else outs[0])
            _sa.note("device", _sa.clock() - sa_t2)
        self._fused_states = new_states
        self._fused_metric_scalars = [
            (m, int(np.prod(arg_vals[nm].shape)))
            for m, (_, nm) in zip(mets, self._fused_metric_pairs)]
        # the counts are valid only for THIS batch's labels: hold the
        # label objects themselves (bare id()s could be reused by the
        # allocator after the batch dies and wrongly match new labels)
        self._fused_metric_labels = list(data_batch.label or [])
        ad = exe.arg_dict
        for nm in self._fused_watched:
            ad[nm]._set(new_w[nm])
        if grads is not None:             # MXNET_FUSED_KEEP_GRADS=1
            gd = exe.grad_dict
            for nm, g in grads.items():
                dst = gd.get(nm)
                if dst is not None:
                    dst._set(g.astype(dst.dtype))
        if new_aux:
            xd = exe.aux_dict
            for nm, val in new_aux.items():
                xd[nm]._set(val)
        exe._outputs = [NDArray(o, ctx=self.contexts[0]) for o in outs]
        exe._pending = None
        if exe._sentinel is not None:
            # grads are fresh only under KEEP_GRADS (otherwise the bound
            # buffers hold the arming-time NaN poison, not real values)
            exe._sentinel.check_executor(exe, grads_fresh=grads is not None)

    # ------------------------------------------------- K-step scan dispatch
    def scan_ready(self, K):
        """Arm (or confirm) the K-step scan program; False -> the caller
        stays on the single-step path. Structural refusals: no fused
        step, MXNET_FUSED_KEEP_GRADS=1 (stacking K gradient sets would
        multiply the step's memory), or a previous arming failure."""
        if K <= 1 or getattr(self, "_step_core", None) is None:
            return False
        if self._fused_keep_grads or self._scan_failed:
            return False
        if self._scan_K == K and self._scan_prog is not None:
            return True
        try:
            self._arm_scan(K)
            return True
        except Exception as exc:
            self.logger.warning(
                "K-step scan arming failed (%s); staying single-step", exc)
            self._scan_failed = True
            return False

    def _arm_scan(self, K):
        """Build (or fetch from the program cache) the jitted program
        running K fused steps inside one ``lax.scan`` — ONE host→device
        dispatch per K batches. Params / optimizer states / rng key ride
        the carry (donated); per-step outputs and metric counts come
        back stacked as ys so metrics and callbacks still see per-batch
        numbers."""
        step_core = self._step_core
        watched = self._fused_watched

        def scan_fn(w, states, key, aux_vals, rest_static, xs):
            def body(carry, x):
                w, states, key, aux = carry
                rest = dict(rest_static)
                rest.update(x["in"])
                (outs, new_aux, new_w, new_states, _grads, key,
                 mets, health) = step_core(w, rest, aux, key, states,
                                           x["lr"], x["wd"])
                if new_aux:
                    aux = {**aux, **new_aux}
                return (new_w, new_states, key, aux), (outs, mets, health)

            w0 = w
            (w, states, key, aux), (outs_s, mets_s, health_s) = \
                jax.lax.scan(body, (w, states, key, aux_vals), xs)
            if health_s is not None:
                # window-level param stats over the K-step delta: one
                # amortised pass instead of a per-step read that would
                # break the donated in-place carry (see
                # _window_param_stats)
                health_s = _window_param_stats(health_s, w0, w, watched)
            return w, states, key, aux, outs_s, mets_s, health_s

        gkey = None
        if self._fused_cache_key is not None:
            gkey = self._fused_cache_key + ("scan", K)
            fn = _progcache.get(gkey)
            if fn is not None:
                if _telemetry.enabled():
                    _telemetry.counter("executor.jit_cache.hit").inc()
                self._scan_prog, self._scan_K = fn, K
                self._register_scan(K)
                return
        if _telemetry.enabled():
            _telemetry.counter("executor.jit_cache.miss").inc()
        # a remat policy extends donation to the aux carry: the scan
        # body threads the FULL aux dict through the carry, so every
        # entry comes back as a (possibly aliased) output buffer and the
        # cells re-point at it — safe without the per-entry cover check
        # the single step needs
        donate = (0, 1, 2, 3) if getattr(
            self, "_remat_policy", "none") != "none" else (0, 1, 2)
        scan_fn.__name__ = self.executor.program_name(f"scan{K}_step")
        fn = _telemetry.wrap_dispatch(
            jax.jit(scan_fn, donate_argnums=donate), "scan_step")
        if gkey is not None:
            _progcache.put(gkey, fn)
        self._scan_prog, self._scan_K = fn, K
        self._register_scan(K)

    def _register_scan(self, K):
        _telemetry.optable.register_program(
            self.executor.program_name(f"scan{K}_step"), self,
            "scan_step", steps=K)

    def _stacked_placement(self, shape):
        """Where a (K, batch, ...) stacked array goes: the scan axis
        stays unsharded, the batch axis shards over the mesh."""
        if self._mesh is None:
            return jax.sharding.SingleDeviceSharding(
                self.contexts[0].jax_device())
        if self._spmd_plan is not None:
            return self._spmd_plan.data_sharding_for(shape, stacked=True)
        return self._stacked_sharding

    def _place_stacked(self, arr):
        """Device-place a (K, batch, ...) stacked array."""
        return jax.device_put(arr, self._stacked_placement(arr.shape))

    def _stack_window(self, window, K):
        """Per-step input dict {name: (K, batch, ...)} + per-step label
        NDArray lists, from either a StackedDataBatch (iterator already
        stacked, possibly in device memory) or a list of K DataBatches."""
        exe = self.executor
        xs_in = {}
        if hasattr(window, "steps"):            # StackedDataBatch
            slots = list(zip(self.data_names, window.data)) + \
                list(zip(self.label_names, window.label or []))
            labels_per_step = [
                [NDArray(l.asjax()[k]) for l in (window.label or [])]
                for k in range(K)]
        else:                                   # list of K DataBatches
            slots = []
            for i, name in enumerate(self.data_names):
                slots.append((name, [b.data[i] for b in window]))
            n_lab = min(len(b.label or []) for b in window)
            for i, name in enumerate(self.label_names[:n_lab]):
                slots.append((name, [b.label[i] for b in window]))
            labels_per_step = [list(b.label or []) for b in window]
        for name, val in slots:
            dst = exe.arg_dict.get(name)
            if dst is None:
                continue
            if isinstance(val, list):
                val = jnp.stack([v.asjax() if isinstance(v, NDArray)
                                 else jnp.asarray(np.asarray(v))
                                 for v in val])
            else:
                val = val.asjax() if isinstance(val, NDArray) \
                    else jnp.asarray(np.asarray(val))
            xs_in[name] = self._place_stacked(val.astype(dst.dtype))
        return xs_in, labels_per_step

    def scan_step(self, window, lrs_list, wds_list):
        """Run K fused train steps in ONE dispatch; swap the advanced
        params/states/aux/rng in, and queue per-step outputs + metric
        counts for ``advance_scan_step`` so the fit loop can still do
        per-batch bookkeeping."""
        from .. import random as _random
        _sa = _telemetry.stepattr
        sa_on = _sa.active()
        if sa_on:
            sa_t0 = _sa.clock()
        exe = self.executor
        K = len(lrs_list)
        if not self.scan_ready(K):
            raise MXNetError("scan_step called without an armed scan "
                             "program (call scan_ready(K) first)")
        if self._fused_rng_gen != _random.generation():
            # mx.random.seed() since the last dispatch: re-draw the
            # device chain at the window boundary (same rule as
            # fused_step, at window granularity)
            self._fused_key = self._draw_fused_key()
            self._fused_rng_gen = _random.generation()
        xs_in, labels_per_step = self._stack_window(window, K)

        # lr/wd as ONE stacked (K, n_watched) device array per side,
        # cached by value — zero transfers per window on fixed schedules
        lrwd_key = (tuple(tuple(l[nm] for nm in self._fused_watched)
                          for l in lrs_list),
                    tuple(tuple(w[nm] for nm in self._fused_watched)
                          for w in wds_list))
        if self._scan_lrwd[0] != lrwd_key:
            self._scan_lrwd = (
                lrwd_key, jnp.asarray(lrwd_key[0], jnp.float32),
                jnp.asarray(lrwd_key[1], jnp.float32))
        _, lr_arr, wd_arr = self._scan_lrwd

        arg_vals = exe._arg_vals()
        w = {nm: arg_vals.pop(nm) for nm in self._fused_watched}
        rest_static = {nm: v for nm, v in arg_vals.items()
                       if nm not in xs_in}
        if sa_on:
            sa_t1 = _sa.clock()
            _sa.note("assemble", sa_t1 - sa_t0)
        (new_w, new_states, self._fused_key, new_aux, outs_s,
         mets_s, health_s) = self._scan_prog(
            w, self._fused_states, self._fused_key, exe._aux_vals(),
            rest_static, {"in": xs_in, "lr": lr_arr, "wd": wd_arr})
        self._last_health = health_s      # (K,)-stacked stats (or None)
        if sa_on:
            sa_t2 = _sa.clock()
            _sa.note("dispatch", sa_t2 - sa_t1)
            # one wait per K batches, for the window before this one
            self._wait_for_step_before(mets_s[0] if mets_s else outs_s[0])
            _sa.note("device", _sa.clock() - sa_t2)
        self._fused_states = new_states
        ad = exe.arg_dict
        for nm in self._fused_watched:
            ad[nm]._set(new_w[nm])
        xd = exe.aux_dict
        for nm, val in new_aux.items():
            if nm in xd:
                xd[nm]._set(val)
        exe._pending = None
        self._fused_metric_scalars = None

        sizes = [int(np.prod(xs_in[nm].shape[1:])) if nm in xs_in
                 else int(np.prod(exe.arg_dict[nm].shape))
                 for (_, nm) in self._fused_metric_pairs]
        self._scan_results = collections.deque(
            (k, outs_s,
             [(mets_s[j][k], sizes[j]) for j in range(len(mets_s))],
             labels_per_step[k])
            for k in range(K))
        if exe._sentinel is not None:
            # window-granularity tripwire on the final step's outputs
            # (params already advanced K steps; per-op attribution needs
            # the staged path, as with the single fused step)
            exe._outputs = [NDArray(o[K - 1], ctx=self.contexts[0])
                            for o in outs_s]
            exe._sentinel.check_executor(exe, grads_fresh=False)

    def advance_scan_step(self):
        """Expose the next scanned step's outputs/metric counts as if a
        single fused step had just run; returns that step's labels."""
        k, outs_s, scalars, labels = self._scan_results.popleft()
        exe = self.executor
        exe._outputs = [NDArray(o[k], ctx=self.contexts[0])
                        for o in outs_s]
        self._fused_metric_scalars = scalars
        self._fused_metric_labels = labels
        return labels

    # windows of undrained health stats the device may still be
    # computing; past this the oldest is forced through (bounds memory
    # and detection lag when the host runs far ahead of the device)
    _HEALTH_LAG_MAX = 4

    def take_health(self, cursor=(0, 0), flush=False):
        """Drain in-program health stats as a list of
        ``(stat_dict, epoch, nbatch)`` per-step tuples (None when
        nothing is ready / the program wasn't armed).

        Stats queue behind the dispatch that produced them and are read
        back only once the device reports them finished
        (``Array.is_ready()``) — the fit loop never hard-syncs
        mid-epoch, so an eager device_get here would block on in-flight
        windows and serialize the host behind the device (the
        readiness gate avoids that). The backlog is bounded by
        ``_HEALTH_LAG_MAX`` windows; ``flush=True`` drains everything —
        the epoch-end call, where the loop syncs anyway. ``cursor`` is
        ``(epoch, first_nbatch)`` of the just-dispatched window, handed
        back alongside its stats so observations attribute to the
        batches that produced them however late they drain."""
        q = getattr(self, "_health_queue", None)
        if q is None:
            q = self._health_queue = collections.deque()
        if self._last_health is not None:
            q.append((self._last_health, cursor))
            self._last_health = None
        out = []
        while q:
            h, (ep, nb) = q[0]
            if not flush and len(q) <= self._HEALTH_LAG_MAX:
                try:
                    # one leaf speaks for the window: every stat comes
                    # out of the same dispatch
                    if not h["grad_norm"].is_ready():
                        break
                except AttributeError:
                    pass        # host-side array: always ready
            q.popleft()
            for k, stats in enumerate(self._health_records(h) or ()):
                out.append((stats, ep, nb + k))
        return out or None

    @staticmethod
    def _health_records(h):
        """Decode one stashed health pytree into per-step host dicts.

        grad_norm / loss / nonfinite are per-step (K-stacked on the
        scan path); param_norm / update_ratio are one window-level
        reading (see ``_window_param_stats``), repeated onto each of
        the window's records so every observation carries the full
        stat set."""
        if h is None:
            return None
        vals = jax.device_get(h)
        gn = np.asarray(vals["grad_norm"])
        loss = np.asarray(vals["loss"])
        pn = float(np.asarray(vals["param_norm"]))
        ur = float(np.asarray(vals["update_ratio"]))
        if gn.ndim == 0:
            return [{"grad_norm": float(gn), "param_norm": pn,
                     "update_ratio": ur,
                     "nonfinite": float(vals["nonfinite"]),
                     "loss": [float(x) for x in np.ravel(loss)]}]
        nf = np.asarray(vals["nonfinite"])
        return [{"grad_norm": float(gn[k]), "param_norm": pn,
                 "update_ratio": ur, "nonfinite": float(nf[k]),
                 "loss": [float(x) for x in np.ravel(loss[k])]}
                for k in range(gn.shape[0])]

    # -------------------------------------------------------------- params
    def adopt_param_dtypes(self, arg_params):
        """Bind-at-the-dtype-given for a group that is already bound:
        every float32 parameter cell whose value in ``arg_params`` is
        handed over at the compute width (``compute_width_params``) is
        re-allocated at it, so ``set_params`` stores it as given and
        the step programs cast nothing. The executor's program-cache
        key follows the new dtypes."""
        names = compute_width_params(arg_params, self.compute_dtype)
        ad = self.executor.arg_dict
        changed = False
        for name, dtype in names.items():
            cell = ad.get(name)
            if cell is None or name not in self.param_names \
                    or cell.dtype != np.float32:
                continue
            cell._set(self._place(jnp.zeros(cell.shape, dtype), "param",
                                  name))
            changed = True
        if changed:
            self.executor.refresh_program_key()

    def set_params(self, arg_params, aux_params):
        """reference: executor_group.py set_params -> copy into the bound
        arrays, preserving sharded placement."""
        fused = getattr(self, "_fused_prog", None) is not None
        ad = self.executor.arg_dict
        for name, arr in arg_params.items():
            if name in ad:
                val = arr.asjax() if isinstance(arr, NDArray) \
                    else jnp.asarray(arr)
                if val.dtype != ad[name].dtype:
                    # a value wider than its cell (a serving binding's,
                    # serving_width_params) lands on the device first,
                    # one parameter at a time: the cast below is then
                    # the convert the step programs ran, not the host's
                    val = self._place(val, "param", name)
                val = self._place(val.astype(ad[name].dtype), "param",
                                  name)
                if fused and name in self._fused_watched:
                    # the fused step donates its param inputs; astype/
                    # device_put are identity when dtype+placement already
                    # match, which would alias the caller's buffer into a
                    # donated argument — force exclusive ownership, same
                    # as the arming-time copy
                    val = jnp.array(val, copy=True)
                ad[name]._set(val)
        xd = self.executor.aux_dict
        for name, arr in (aux_params or {}).items():
            if name in xd:
                val = arr.asjax() if isinstance(arr, NDArray) \
                    else jnp.asarray(arr)
                xd[name]._set(self._place(val.astype(xd[name].dtype),
                                          "param", name))

    def get_params(self, arg_params, aux_params):
        """Copy params out (device->host). reference: executor_group.py."""
        for name in self.param_names:
            arg_params[name] = self.executor.arg_dict[name].copy()
        for name in self.aux_names:
            aux_params[name] = self.executor.aux_dict[name].copy()

    # -------------------------------------------------------------- forward
    def forward(self, data_batch, is_train=None):
        """Load the full batch sharded over the mesh and run.

        reference: executor_group.py:355-379 _load_data + per-exec forward;
        here the shard happens in jax.device_put (host->HBM splits, which
        overlap with compute thanks to async dispatch).
        """
        if is_train is None:
            is_train = self.for_training
        # any staged execution invalidates fused-step metric scalars so a
        # later update_metric (e.g. an eval pass) can never consume
        # counts from a previous train batch; pending scanned steps are
        # dropped for the same reason, as are undrained health stats
        self._fused_metric_scalars = None
        self._last_health = None
        if getattr(self, "_health_queue", None):
            self._health_queue.clear()
        if getattr(self, "_scan_results", None):
            self._scan_results.clear()
        self._load_batch(data_batch)
        self.executor.forward(is_train=is_train)

    def _lies_as_data(self, val):
        """Does the array lie where ``_place(val, "data")`` would put
        it: committed to the one device, or under a sharding over the
        mesh that lays the same rows on the same chips?"""
        if not val.committed:
            return False
        want = self._data_sharding
        if self._spmd_plan is not None:
            want = self._spmd_plan.data_sharding_for(val.shape)
        have = val.sharding
        return have == want or have.is_equivalent_to(want, val.ndim)

    def input_stager(self):
        """``stage(hosts) -> [NDArray]`` for a caller that makes its
        batch on the host every step (the decode drivers): each host
        array, in ``data_names``' order, put once - at its input cell's
        width (read here, once) and where ``forward`` places its batch
        - so that ``_load_batch`` takes it as it is and the step's
        launch is the jitted call. An entry that lies on the device
        already (the ids an earlier step's select program left there)
        is handed on untouched: no put, and ``_load_batch`` judges it
        like any other."""
        cells = self.executor.arg_dict
        dtypes = [cells[nm].dtype for nm in self.data_names]
        place, ctx = self._place, self.contexts[0]

        def stage(hosts):
            return [NDArray(h if isinstance(h, jax.Array)
                            else place(h.astype(dt), "data"), ctx=ctx)
                    for h, dt in zip(hosts, dtypes)]
        return stage

    def _load_batch(self, data_batch):
        """Shard the batch's data (and labels, which eval graphs read)
        into the bound input arrays. An input that is already a device
        array of its cell's dtype under the cell's placement is taken as
        it is (the cell aliases the caller's buffer, which no program
        donates: see ``setup_fused_step``); anything else is converted
        and put. ``io.load_batch.aliased`` / ``.puts`` count the two."""
        load_span = _telemetry.span("io.load_batch")
        cells = self.executor.arg_dict
        aliased = puts = 0

        def load(names, arrays):
            nonlocal aliased, puts
            for name, arr in zip(names, arrays):
                dst = cells.get(name)
                if dst is None:
                    continue
                val = arr.asjax() if isinstance(arr, NDArray) else arr
                if isinstance(val, jax.Array) and val.dtype == dst.dtype \
                        and not val.weak_type and self._lies_as_data(val):
                    dst._set(val)
                    aliased += 1
                    continue
                if not isinstance(val, jax.Array):
                    val = jnp.asarray(np.asarray(val))
                dst._set(self._place(val.astype(dst.dtype), "data"))
                puts += 1

        with load_span:
            load(self.data_names, data_batch.data)
            if self.label_names and data_batch.label:
                load(self.label_names, data_batch.label)
        if _telemetry.enabled():
            n_aliased, n_puts = self._load_counters()
            n_aliased.inc(aliased)
            n_puts.inc(puts)

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True"
        self.executor.backward(out_grads=out_grads)

    # -------------------------------------------------------------- outputs
    def get_outputs(self, merge_multi_context=True):
        outs = self.executor.outputs
        if merge_multi_context:
            return outs
        return [[o] for o in outs]

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [self.executor.grad_dict[name] for name in self.data_names]
        if merge_multi_context:
            return grads
        return [[g] for g in grads]

    def update_metric(self, eval_metric, labels):
        """reference: executor_group.py:510 — metric on device outputs.

        After a fused step, plain Accuracy consumes the correct-counts
        the program already computed (zero extra dispatches); every
        other metric takes the general path on the outputs."""
        from ..metric import Accuracy
        scalars = getattr(self, "_fused_metric_scalars", None)
        if (scalars and type(eval_metric) is Accuracy
                and eval_metric.num is None
                and len(scalars) == len(labels or [])
                # same label/output count contract the staged path's
                # check_label_shapes enforces — never mask a violation
                and len(labels) == len(self.executor.outputs)
                # the counts belong to the fused batch's label objects;
                # a caller scoring different labels gets the general path
                and len(labels) == len(self._fused_metric_labels)
                and all(a is b for a, b in
                        zip(labels, self._fused_metric_labels))):
            self._fused_metric_scalars = None
            for correct, size in scalars:
                eval_metric._accumulate_device(correct, size)
            return
        eval_metric.update(labels, self.executor.outputs)

    def get_states(self, merge_multi_context=True):
        assert not self.state_names
        return []

    def set_states(self, states=None, value=None):
        pass

    def install_monitor(self, mon):
        mon.install_exe(self.executor)

    def install_sentinel(self, sentinel, per_op=False):
        sentinel.install(self.executor, per_op=per_op)

    def bind_exec(self, data_shapes, label_shapes, shared_group=None,
                  reshape=False):
        self.data_shapes = [x if isinstance(x, DataDesc) else DataDesc(*x)
                            for x in data_shapes]
        if label_shapes is not None:
            self.label_shapes = [x if isinstance(x, DataDesc)
                                 else DataDesc(*x) for x in label_shapes]
        self._bind_exec(shared_group)
