"""Module: the primary training interface over one Symbol.

Behavioral parity with reference python/mxnet/module/module.py, written
for this framework's execution model: ONE mesh-sharded executor instead
of a list of per-device executors, so parameter handling is a flat
name->NDArray mapping throughout and the update path walks
``zip(param_names, param_arrays, grad_arrays)`` with stride 1.
"""
from __future__ import annotations

import logging
import pickle

import numpy as np

from .. import ndarray as nd
from .. import optimizer as opt
from ..base import MXNetError
from ..context import current_context
from ..initializer import Uniform
from ..model import (_create_kvstore, _initialize_kvstore, load_checkpoint)
from ..ndarray import NDArray
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


class Module(BaseModule):
    """Train/predict over a single Symbol bound to a (possibly multi-
    device) context list. reference: module/module.py:40-700."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, compute_dtype=None, param_dtypes=None):
        """``param_dtypes`` (``{name: dtype}``) binds those parameter
        cells at another dtype than float32 - for a caller that knows
        before ``bind`` that its parameters come at the compute width
        (``executor_group.compute_width_params``; ``init_params`` finds
        the same out after bind) or that it only ever serves them
        (``executor_group.serving_width_params``: ``DecodeEngine``)."""
        super().__init__(logger=logger)
        self._compute_dtype = compute_dtype
        self._param_dtypes = dict(param_dtypes or {})
        context = context if context is not None else [current_context()]
        self._context = list(context) if isinstance(context, (list, tuple)) \
            else [context]
        self._work_load_list = work_load_list or [1] * len(self._context)

        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._state_names = list(state_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        self._output_names = symbol.list_outputs()
        self._aux_names = symbol.list_auxiliary_states()
        inputs = set(self._data_names) | set(self._label_names)
        self._param_names = [a for a in symbol.list_arguments()
                             if a not in inputs]
        _check_input_names(symbol, self._data_names, "data", True)
        _check_input_names(symbol, self._label_names, "label", False)
        _check_input_names(symbol, self._state_names, "state", True)
        _check_input_names(symbol, self._fixed_param_names, "fixed_param",
                           True)

        self._exec_group = None
        self._arg_params = None
        self._aux_params = None
        self._params_dirty = False
        self._grad_req = None
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._fused_armed = False
        self._fused_done = False
        self._steps_per_dispatch = 1
        self._zero_stage = None         # None -> MXNET_ZERO_STAGE, else 0
        self._spmd = None               # None -> MXNET_SPMD at bind time
        self._mesh_config = None        # parallel.MeshConfig (spmd mode)
        self._remat = None              # None -> MXNET_REMAT_POLICY

    # ------------------------------------------------------------ checkpoint
    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Build a Module from a saved checkpoint (symbol JSON + params)."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        return mod

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Write prefix-symbol.json + prefix-NNNN.params (+ .states)."""
        self._symbol.save(f"{prefix}-symbol.json")
        self.save_params(f"{prefix}-{epoch:04d}.params")
        if save_optimizer_states:
            self.save_optimizer_states(f"{prefix}-{epoch:04d}.states")

    # ------------------------------------------------------------ properties
    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._exec_group.data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._exec_group.label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        known = {d.name: d.shape for d in self._exec_group.data_shapes}
        for l in self._exec_group.label_shapes or []:
            known[l.name] = l.shape
        _, out_shapes, _ = self._symbol.infer_shape(**known)
        return list(zip(self._output_names, out_shapes))

    # ---------------------------------------------------------------- params
    def get_params(self):
        assert self.binded and self.params_initialized
        if self._params_dirty:
            self._sync_params_from_devices()
        return self._arg_params, self._aux_params

    def init_params(self, initializer=Uniform(0.01), arg_params=None,
                    aux_params=None, allow_missing=False, force_init=False):
        """Fill parameter arrays from the caches and/or the initializer."""
        if self.params_initialized and not force_init:
            return
        assert self.binded, "bind() must run before init_params()"

        # bind at the dtype given: a parameter handed over at the
        # compute width is stored as it is, not as a float32 master
        self._exec_group.adopt_param_dtypes(arg_params)
        exe = self._exec_group.executor
        if self._arg_params is None:
            self._arg_params = {
                n: nd.zeros(exe.arg_dict[n].shape,
                            dtype=exe.arg_dict[n].dtype)
                for n in self._param_names}
        if self._aux_params is None:
            self._aux_params = {
                n: nd.zeros(a.shape, dtype=a.dtype)
                for n, a in exe.aux_dict.items()}

        def fill(name, arr, cache):
            if cache is None:
                initializer(name, arr)
            elif name in cache:
                src = cache[name]
                if src is not arr:
                    if isinstance(src, NDArray):
                        src.copyto(arr)
                    else:
                        arr._set(np.asarray(src))
            elif not allow_missing:
                raise RuntimeError(
                    f"parameter {name!r} missing from the provided params "
                    "(pass allow_missing=True to initialize it instead)")
            elif initializer is not None:
                initializer(name, arr)

        for name in sorted(self._arg_params):
            fill(name, self._arg_params[name], arg_params)
        for name in sorted(self._aux_params):
            fill(name, self._aux_params[name], aux_params)

        self.params_initialized = True
        self._exec_group.set_params(self._arg_params, self._aux_params)
        # a value stored into a cell of another dtype (a serving
        # binding's cells at the compute width) is no longer what the
        # cache holds: the next get_params reads the cells back
        self._params_dirty = any(
            exe.arg_dict[n].dtype != a.dtype
            for n, a in self._arg_params.items())

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=False,
                             force_init=force_init)
            return
        if self.params_initialized and not force_init:
            return
        self._exec_group.set_params(arg_params, aux_params)
        self._params_dirty = True
        self.params_initialized = True

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # ------------------------------------------------------------------ bind
    def _resolve_spmd(self, explicit=None):
        """SPMD mode: explicit bind arg > fit kwarg (self._spmd) >
        MXNET_SPMD env; default off (the kvstore-era arrangement)."""
        import os
        if explicit is not None:
            return bool(explicit)
        if self._spmd is not None:
            return bool(self._spmd)
        return os.environ.get("MXNET_SPMD", "").lower() in \
            ("1", "true", "yes", "on")

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write", spmd=None, mesh=None):
        """Compile the symbol into the sharded executor group.

        ``spmd=True`` (or ``MXNET_SPMD=1`` / ``fit(spmd=True)``) binds
        the GSPMD arrangement: one program over the named mesh from
        ``mesh`` (a ``parallel.MeshConfig``; default ``MXNET_MESH_*``
        env, else a 1-D data axis over the contexts), params sharded per
        the symbol's ctx_group tags on the model axis, gradient
        collectives emitted by XLA from the sharding specs — the
        kvstore becomes optional (docs/performance.md).
        """
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Module is already bound; ignoring bind() "
                                "(use force_rebind=True to re-bind)")
            return
        if not for_training:
            assert not inputs_need_grad

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._grad_req = grad_req
        if mesh is not None:
            self._mesh_config = mesh
        self._spmd_active = self._resolve_spmd(spmd)

        shared_group = None
        if shared_module is not None:
            assert isinstance(shared_module, Module) and \
                shared_module.binded and shared_module.params_initialized
            shared_group = shared_module._exec_group

        self._exec_group = DataParallelExecutorGroup(
            self._symbol, self._context, self._work_load_list, data_shapes,
            label_shapes, self._param_names, for_training, inputs_need_grad,
            shared_group, logger=self.logger,
            fixed_param_names=self._fixed_param_names, grad_req=grad_req,
            state_names=self._state_names,
            compute_dtype=self._compute_dtype,
            spmd=self._spmd_active, mesh_config=self._mesh_config,
            param_dtypes=self._param_dtypes)

        if shared_module is not None:
            self.params_initialized = True
            self._arg_params = shared_module._arg_params
            self._aux_params = shared_module._aux_params
        elif self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _reset_bind(self):
        self.binded = False
        self._exec_group = None

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._exec_group.bind_exec(data_shapes, label_shapes, reshape=True)
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # ------------------------------------------------------------- optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Resolve the kvstore/updater arrangement and build the optimizer."""
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer is already initialized; "
                                "ignoring init_optimizer()")
            return

        # SPMD mode: the gradient collectives live inside the jitted
        # program (XLA emits them from the sharding specs) — a local/
        # device kvstore would be a second, redundant reduction plan, so
        # it is dropped; dist_* stores keep owning cross-process
        # reduction (the mesh here is single-process) and disable spmd's
        # in-program arrangement via the normal fused-step gating.
        spmd_plan = getattr(self._exec_group, "_spmd_plan", None)
        if spmd_plan is not None and kvstore is not None:
            kv_type = kvstore if isinstance(kvstore, str) \
                else getattr(kvstore, "type", "")
            if "dist" in kv_type:
                self.logger.warning(
                    "spmd mode with a %r kvstore: cross-process "
                    "reduction stays on the kvstore path (the in-program "
                    "collectives cover this process's mesh only)", kv_type)
            else:
                self.logger.info(
                    "spmd mode: %r kvstore dropped — gradient "
                    "collectives are emitted by XLA from the mesh "
                    "sharding specs", kv_type)
                kvstore = None

        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), self._arg_params)

        # dist_sync semantics: every worker sees the global batch
        batch_size = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_sync" in kvstore.type:
            batch_size *= kvstore.num_workers

        if isinstance(optimizer, str):
            params = dict(optimizer_params)
            params.setdefault("rescale_grad", 1.0 / batch_size)
            optimizer = opt.create(
                optimizer, sym=self.symbol,
                param_idx2name=dict(enumerate(self._param_names)),
                **params)
        else:
            assert isinstance(optimizer, opt.Optimizer)

        self._optimizer = optimizer
        self._kvstore = kvstore
        self._update_on_kvstore = update_on_kvstore
        self._updater = None if update_on_kvstore \
            else opt.get_updater(optimizer)

        # Fused train step: forward+backward+update as ONE XLA program
        # (reference bulk-exec segments + fused optimizer_op.cc). Armed
        # only when the update is single-process local — a dist kvstore
        # or server-side updater owns the math in those arrangements.
        # zero_stage=1 (fit kwarg or MXNET_ZERO_STAGE) selects the
        # in-program reduce-scatter + sharded-state update plan.
        import os
        zero_stage = self._zero_stage
        if zero_stage is None:
            zero_stage = int(os.environ.get("MXNET_ZERO_STAGE", "0") or 0)
        self._fused_armed = False
        self._fused_done = False
        if (not update_on_kvstore
                and (kvstore is None or "dist" not in kvstore.type)
                and self._exec_group.executor._monitor_callback is None):
            self._fused_armed = bool(
                self._exec_group.setup_fused_step(optimizer,
                                                  zero_stage=zero_stage,
                                                  remat=self._remat))
        if spmd_plan is not None and not self._fused_armed:
            self.logger.warning(
                "spmd requested but the fused train step could not arm "
                "(monitor/NaiveEngine/non-fusable optimizer or grad_req, "
                "or a dist kvstore); the staged per-phase path runs over "
                "the mesh instead")

        if kvstore:
            _initialize_kvstore(kvstore=kvstore,
                                param_arrays=self._exec_group.param_arrays,
                                arg_params=self._arg_params,
                                param_names=self._param_names,
                                update_on_kvstore=update_on_kvstore)
            if update_on_kvstore:
                kvstore.set_optimizer(optimizer)

        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

        # the donation/collective hazard surface only exists once the
        # fused/ZeRO plans are armed and the kvstore is attached —
        # re-run the static-analysis passes over the full arrangement
        # (MXNET_GRAPH_VALIDATE=warn|raise; bind() already verified the
        # bare graph)
        from .. import analysis as _analysis
        if _analysis.resolve_mode(None) is not None:
            _analysis.validate_module(self)

    def borrow_optimizer(self, shared_module):
        """Share optimizer state with another Module (bucketing)."""
        assert shared_module.optimizer_initialized
        self._optimizer = shared_module._optimizer
        self._kvstore = shared_module._kvstore
        self._update_on_kvstore = shared_module._update_on_kvstore
        self._updater = shared_module._updater
        # shared optimizer state lives in the updater — the fused path
        # keeps per-group device state, so bucketing stays staged
        self._fused_armed = False
        self._fused_done = False
        self.optimizer_initialized = True

    # ------------------------------------------------------------ train step
    def forward_backward(self, data_batch):
        """One training pass; routes through the fused fwd+bwd+update
        program when armed. The weight update then happens inside this
        call (the subsequent ``update()`` is a no-op for the batch), so
        a loop that conditionally skips ``update()`` must first disarm
        with ``install_monitor`` absent via the staged path. The fused
        program does not emit per-param gradients (they cost ~5% of the
        step as extra XLA outputs); set ``MXNET_FUSED_KEEP_GRADS=1`` to
        keep ``grad_dict`` populated, or install a monitor to fall back
        to the staged path, which always populates it."""
        if self._fused_armed and self.optimizer_initialized:
            if self._exec_group.executor._monitor_callback is not None:
                # a monitor was installed directly on the executor after
                # arming — migrate to the staged path for good so the
                # optimizer state lives in exactly one place
                self._defuse()
            else:
                self._exec_group.fused_step(data_batch,
                                            *self._fused_lr_wd())
                self._fused_done = True
                return
        self.forward(data_batch, is_train=True)
        self.backward()

    def _fused_lr_wd(self):
        """Per-step host-side lr/wd per watched param (scheduler, mults,
        Adam bias correction) — the traced scalars the fused program
        takes each dispatch. Ordering matches the staged Optimizer
        .update: lr/wd are read BEFORE the update count advances, the
        bias-correction step count after."""
        o = self._optimizer
        watched = set(self._exec_group._fused_watched)
        lrs, wds = {}, {}
        scale = getattr(o, "fused_lr_scale", None)
        for i, nm in enumerate(self._param_names):
            if nm not in watched:
                continue
            lr = o._get_lr(i)
            wds[nm] = o._get_wd(i)
            o._update_count(i)
            if scale is not None:
                lr *= scale(o._index_update_count[i])
            lrs[nm] = lr
        return lrs, wds

    def _defuse(self):
        """Disarm the fused path, migrating its device optimizer state
        into the staged updater so training numerics continue exactly
        (ZeRO-sharded states unflatten back to param shape first)."""
        import jax
        fs = self._exec_group.defused_states()
        for i, nm in enumerate(self._param_names):
            if nm not in fs:
                continue
            leaves = jax.tree.leaves(fs[nm])
            if not leaves:
                state = None
            elif isinstance(fs[nm], (tuple, list)):
                state = tuple(NDArray(l) for l in leaves)
            else:
                state = NDArray(leaves[0])
            self._updater.states[i] = state
        self._fused_armed = False

    # --------------------------------------------------- K-step scan window
    def _scan_window_size(self):
        """Batches per dispatch for the scan-fused fit loop (1 = the
        plain per-batch loop). >1 only when the fused step is armed, no
        monitor claims per-op taps, and the scan program arms."""
        K = getattr(self, "_steps_per_dispatch", 1)
        if K <= 1 or not self._fused_armed or not self.optimizer_initialized:
            return 1
        if self._exec_group.executor._monitor_callback is not None:
            return 1
        if not self._exec_group.scan_ready(K):
            return 1
        return K

    def _run_scan_window(self, window):
        """Advance K batches in one scan dispatch. lr/wd/update-counts
        are read per step host-side first (identical scheduler semantics
        to K single fused steps), then the whole window executes as one
        XLA program."""
        K = window.steps if hasattr(window, "steps") else len(window)
        lrs_list, wds_list = [], []
        for _ in range(K):
            lrs, wds = self._fused_lr_wd()
            lrs_list.append(lrs)
            wds_list.append(wds)
        self._exec_group.scan_step(window, lrs_list, wds_list)
        self._params_dirty = True

    def _advance_scan_batch(self):
        return self._exec_group.advance_scan_step()

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._exec_group.forward(data_batch, is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply one optimizer step to every trainable parameter.

        Two arrangements (reference model.py:88-116 semantics, flat here):
        update_on_kvstore — push grad / pull weight, the store's updater
        does the math; otherwise — optional kvstore grad all-reduce, then
        the local updater writes the weights in place.
        """
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        if self._fused_done:
            # weights/state already advanced inside the fused program
            self._fused_done = False
            return
        if self._fused_armed:
            # caller is driving forward/backward/update manually (e.g.
            # BucketingModule) — migrate to the staged arrangement so
            # optimizer state lives in exactly one place
            self._defuse()
        weights = self._exec_group.param_arrays
        grads = self._exec_group.grad_arrays
        idxs = [i for i, g in enumerate(grads) if g is not None]
        if not idxs:
            return
        if self._kvstore:
            # ONE multi-key push in reverse execution order — the order
            # backward produces gradients — with matching priorities, so
            # the dist store's bucket scheduler dispatches each bucket's
            # collective as soon as its grads exist (overlapping with
            # the still-draining backward program) instead of one
            # serial reduce per key. Pulls then run forward-order
            # (priority=-i): early layers land first for the next
            # forward, the reference's pull-priority contract.
            rev = idxs[::-1]
            self._kvstore.push(rev, [grads[i] for i in rev],
                               priority=rev)
            if self._update_on_kvstore:
                self._kvstore.pull(idxs, [weights[i] for i in idxs],
                                   priority=[-i for i in idxs])
                return
            self._kvstore.pull(idxs, [grads[i] for i in idxs],
                               priority=[-i for i in idxs])
        if self._update_on_kvstore:
            # update_on_kvstore without a store cannot happen
            # (_create_kvstore forces it False when kv is None)
            raise MXNetError("update_on_kvstore set without a kvstore")
        for i in idxs:
            self._updater(i, grads[i], weights[i])

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    # ------------------------------------------------------ optimizer states
    def _opt_counts(self):
        """Name-keyed update counts + the global count — the half of the
        optimizer's state that is NOT per-param arrays (Adam bias
        correction, lr schedules). Without these a restored run replays
        update 1's bias correction and warmup lr over trained weights."""
        o = self._optimizer
        return {
            "num_update": int(o.num_update),
            "index_update_count": {
                self._param_names[i]: int(c)
                for i, c in o._index_update_count.items()
                if 0 <= i < len(self._param_names)},
        }

    def _restore_opt_counts(self, counts):
        o = self._optimizer
        o.num_update = int(counts.get("num_update", o.num_update))
        idx = {nm: i for i, nm in enumerate(self._param_names)}
        for nm, c in (counts.get("index_update_count") or {}).items():
            if nm in idx:
                o._index_update_count[idx[nm]] = int(c)

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
            return
        def host(v):
            if isinstance(v, NDArray):
                return v.asnumpy()
            if isinstance(v, (tuple, list)):
                return [host(x) for x in v]
            return v
        if self._fused_armed:
            # export always writes param-shaped host arrays: replicated
            # and ZeRO-sharded arrangements produce the same checkpoint
            states = {"__fused__": self._exec_group.export_fused_states()}
        else:
            states = {k: host(v) for k, v in self._updater.states.items()}
        payload = {"__format__": 2, "states": states,
                   **self._opt_counts()}
        with open(fname, "wb") as fout:
            pickle.dump(payload, fout)

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            return
        with open(fname, "rb") as fin:
            states = pickle.load(fin)
        if isinstance(states, dict) and states.get("__format__") == 2:
            self._restore_opt_counts(states)
            states = states["states"]
        import jax
        if "__fused__" in states and self._fused_armed:
            self._exec_group.import_fused_states(states["__fused__"])
        elif "__fused__" in states:
            # fused-format checkpoint into a staged module: unwrap to the
            # updater's per-index states
            for i, nm in enumerate(self._param_names):
                if nm not in states["__fused__"]:
                    continue
                leaves = jax.tree.leaves(states["__fused__"][nm])
                if not leaves:
                    st = None
                elif isinstance(states["__fused__"][nm], (tuple, list)):
                    st = tuple(NDArray(jnp_arr) for jnp_arr in
                               map(np.asarray, leaves))
                else:
                    st = NDArray(np.asarray(leaves[0]))
                self._updater.states[i] = st
        elif self._fused_armed:
            # staged-format checkpoint into a fused module: project each
            # per-index state onto the fused per-name device layout
            # (replicated or ZeRO-sharded; pickled staged tuples come
            # back as lists — import_staged_state walks the structure)
            fs = self._exec_group._fused_states
            for i, nm in enumerate(self._param_names):
                if nm in fs and i in states and jax.tree.leaves(fs[nm]):
                    self._exec_group.import_staged_state(nm, states[i])
        else:
            self._updater.states.update(states)

    def install_monitor(self, mon):
        assert self.binded
        self._exec_group.install_monitor(mon)
        if self._fused_armed:
            # per-op taps need the staged path; carry the optimizer
            # state over so momentum/moments don't reset
            self._defuse()

    def install_sentinel(self, sentinel, per_op=False):
        """Attach a NaN/Inf sentinel (telemetry.NanSentinel) to the bound
        executor. The default executor-level mode works on the fused
        train step; ``per_op=True`` claims the Monitor tap for exact
        op attribution, which forces the staged (eager) path."""
        assert self.binded
        self._exec_group.install_sentinel(sentinel, per_op=per_op)
        if per_op and self._fused_armed:
            self._defuse()
