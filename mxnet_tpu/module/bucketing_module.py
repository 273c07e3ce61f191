"""BucketingModule: variable-length sequence training by graph
specialization.

API parity with reference python/mxnet/module/bucketing_module.py; here
every bucket is its own jitted XLA program (compiled on first use) and
all buckets alias the SAME parameter NDArray cells as the default
bucket's module — no weight copying on bucket switch, the property the
reference engineers via shared memory pools. The jit cache keyed by
bucket is the "bucketed jit caches" design (SURVEY.md §7 M5).

Structure: the *leader* module (default bucket) owns parameters and the
optimizer; the *active* module is whatever bucket the last batch
selected; everything user-facing proxies to one of those two.
"""
from __future__ import annotations

import logging

from .base_module import BaseModule
from .module import Module

__all__ = ["BucketingModule"]


class BucketingModule(BaseModule):
    def __init__(self, sym_gen, default_bucket_key=None, logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)
        if default_bucket_key is None:
            raise ValueError("BucketingModule needs a default_bucket_key")
        self._sym_gen = sym_gen
        self._default_bucket_key = default_bucket_key
        self._module_kwargs = dict(
            logger=logger, context=context, work_load_list=work_load_list,
            fixed_param_names=fixed_param_names, state_names=state_names)
        self._buckets = {}
        self._active_key = None
        self._params_dirty = False

    # ---------------------------------------------------------- plumbing
    def _generate(self, bucket_key):
        ret = self._sym_gen(bucket_key)
        if len(ret) != 3:
            raise ValueError(
                "sym_gen(bucket_key) must return (symbol, data_names, "
                "label_names)")
        return ret

    @property
    def _leader(self):
        return self._buckets[self._default_bucket_key]

    @property
    def _active(self):
        return self._buckets[self._active_key]

    def _reset_bind(self):
        self.binded = False
        self._buckets = {}
        self._active_key = None

    # -------------------------------------------------------- properties
    @property
    def data_names(self):
        if self.binded:
            return self._active.data_names
        return self._generate(self._default_bucket_key)[1]

    @property
    def output_names(self):
        if self.binded:
            return self._active.output_names
        return self._generate(self._default_bucket_key)[0].list_outputs()

    @property
    def data_shapes(self):
        assert self.binded
        return self._active.data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._active.label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._active.output_shapes

    @property
    def symbol(self):
        assert self.binded
        return self._active.symbol

    # ------------------------------------------------------------ params
    def get_params(self):
        assert self.binded and self.params_initialized
        self._active._params_dirty = self._params_dirty
        params = self._active.get_params()
        self._params_dirty = False
        return params

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded
        from ..initializer import Uniform
        self._leader.init_params(
            initializer=initializer or Uniform(0.01),
            arg_params=arg_params, aux_params=aux_params,
            allow_missing=allow_missing, force_init=force_init)
        self.params_initialized = True
        self._params_dirty = self._leader._params_dirty

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)

    # -------------------------------------------------------------- bind
    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if shared_module is not None:
            raise ValueError("BucketingModule cannot itself be shared")
        if force_rebind:
            self._reset_bind()
        if self.binded:
            self.logger.warning("Module is already bound; ignoring bind()")
            return

        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.binded = True
        self._grad_req = grad_req

        sym, data_names, label_names = self._generate(
            self._default_bucket_key)
        leader = Module(sym, data_names, label_names, **self._module_kwargs)
        leader.bind(data_shapes, label_shapes, for_training,
                    inputs_need_grad, grad_req=grad_req)
        self._buckets[self._default_bucket_key] = leader
        self._active_key = self._default_bucket_key

    def switch_bucket(self, bucket_key, data_shapes, label_shapes=None):
        """Select (compiling on first use) the module for ``bucket_key``."""
        assert self.binded, "bind() must run before switch_bucket()"
        if bucket_key not in self._buckets:
            sym, data_names, label_names = self._generate(bucket_key)
            mod = Module(sym, data_names, label_names,
                         **self._module_kwargs)
            mod.bind(data_shapes, label_shapes, self.for_training,
                     self.inputs_need_grad, shared_module=self._leader,
                     grad_req=self._grad_req)
            if self.optimizer_initialized:
                mod.borrow_optimizer(self._leader)
            self._buckets[bucket_key] = mod
        self._active_key = bucket_key

    def warm_buckets(self, bucket_shapes):
        """Bind every bucket in ``bucket_shapes`` up front.

        ``bucket_shapes``: iterable of ``(bucket_key, data_shapes,
        label_shapes)`` triples. Serving warmup calls this so every rung
        of a bucket ladder is bound (and its forward program traced on
        first use through the process-wide program cache) before the
        first request arrives — bucket switches in steady state then
        never construct executors or compile. Restores the previously
        active bucket. Returns the list of bucket keys bound."""
        assert self.binded and self.params_initialized, \
            "bind() + init_params() must run before warm_buckets()"
        prev = self._active_key
        bound = []
        for key, data_shapes, label_shapes in bucket_shapes:
            self.switch_bucket(key, data_shapes, label_shapes)
            bound.append(key)
        self._active_key = prev
        return bound

    @property
    def bucket_keys(self):
        """Keys with a bound module (the warmed rungs)."""
        return list(self._buckets)

    # --------------------------------------------------------- optimizer
    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer is already initialized; "
                                "ignoring init_optimizer()")
            return
        self._leader.init_optimizer(kvstore, optimizer, optimizer_params,
                                    force_init=force_init)
        for mod in self._buckets.values():
            if mod is not self._leader:
                mod.borrow_optimizer(self._leader)
        self.optimizer_initialized = True

    # -------------------------------------------------------- train step
    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self.switch_bucket(data_batch.bucket_key, data_batch.provide_data,
                           data_batch.provide_label)
        self._active.forward(data_batch, is_train=is_train)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._active.backward(out_grads=out_grads)

    def update(self):
        assert self.binded and self.params_initialized and \
            self.optimizer_initialized
        self._params_dirty = True
        self._active.update()

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        return self._active.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.params_initialized and \
            self.inputs_need_grad
        return self._active.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        assert self.binded and self.params_initialized
        self._active.update_metric(eval_metric, labels)

    def install_monitor(self, mon):
        assert self.binded
        for mod in self._buckets.values():
            mod.install_monitor(mon)
