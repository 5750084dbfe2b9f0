"""BucketingModule: one Module per sequence-length bucket, shared params.

API parity with the reference ``python/mxnet/module/bucketing_module.py:35-106``.
TPU note (SURVEY §5.7): each bucket key is simply a distinct jit
specialization — the first batch of a bucket compiles its XLA program, later
batches reuse it; parameters are shared across buckets by name through the
leader (default-bucket) module.
"""
from __future__ import annotations

import logging
import warnings

from .base_module import BaseModule
from .module import Module

__all__ = ["BucketingModule"]


class BucketingModule(BaseModule):
    """Routes each batch to the Module bound for its ``bucket_key``.

    The default bucket's module is the *leader*: it owns the canonical
    parameter dicts and the optimizer; other buckets alias both.
    """

    def __init__(self, sym_gen, default_bucket_key=None, logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)
        if default_bucket_key is None:
            raise ValueError("default_bucket_key is required")
        self._sym_gen = sym_gen
        self._default_bucket_key = default_bucket_key
        self._module_kwargs = dict(
            logger=logger, context=context, work_load_list=work_load_list,
            fixed_param_names=fixed_param_names, state_names=state_names)
        self._by_key = {}
        self._active_key = None
        self._params_dirty = False

    # ---- internals ----

    @property
    def _active(self):
        return self._by_key.get(self._active_key)

    @property
    def _leader(self):
        return self._by_key.get(self._default_bucket_key)

    def _generate(self, bucket_key):
        """Call sym_gen → (symbol, data_names, label_names)."""
        return self._sym_gen(bucket_key)

    def _spawn(self, bucket_key, data_shapes, label_shapes, shared):
        """Create and bind a Module for *bucket_key*."""
        sym, data_names, label_names = self._generate(bucket_key)
        mod = Module(sym, data_names, label_names, **self._module_kwargs)
        mod.bind(data_shapes, label_shapes,
                 for_training=self.for_training,
                 inputs_need_grad=self.inputs_need_grad,
                 shared_module=shared,
                 grad_req=getattr(self, "_grad_req", "write"))
        self._by_key[bucket_key] = mod
        return mod

    # ---- properties (delegate to the active module) ----

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
        self._require_bound()
        return self._active.data_shapes

    @property
    def label_shapes(self):
        self._require_bound()
        return self._active.label_shapes

    @property
    def output_shapes(self):
        self._require_bound()
        return self._active.output_shapes

    @property
    def symbol(self):
        self._require_bound()
        return self._active.symbol

    def _require_bound(self):
        if not self.binded:
            raise AssertionError("BucketingModule is not bound")

    # ---- parameters ----

    def get_params(self):
        self._require_ready()
        self._active._params_dirty = self._params_dirty
        out = self._active.get_params()
        self._params_dirty = False
        return out

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        self._require_bound()
        self._active.init_params(initializer=initializer,
                                 arg_params=arg_params, aux_params=aux_params,
                                 allow_missing=allow_missing,
                                 force_init=force_init,
                                 allow_extra=allow_extra)
        self._params_dirty = False
        self.params_initialized = True

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            self.init_params(initializer=None, arg_params=arg_params,
                             aux_params=aux_params, allow_missing=False,
                             force_init=force_init, allow_extra=allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("set_params ignored: already initialized "
                          "(pass force_init=True to override)", stacklevel=2)
            return
        self._active.set_params(arg_params, aux_params,
                                allow_missing=True, force_init=force_init,
                                allow_extra=allow_extra)
        self._params_dirty, self.params_initialized = True, True

    def get_states(self, merge_multi_context=True):
        self._require_ready()
        return self._active.get_states(merge_multi_context)

    def set_states(self, states=None, value=None):
        self._require_ready()
        self._active.set_states(states, value)

    # ---- binding / bucket switching ----

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if shared_module is not None:
            raise ValueError("BucketingModule does not accept shared_module")
        if force_rebind:
            self.binded = False
            self._by_key, self._active_key = {}, None
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req
        self.binded = True
        self._spawn(self._default_bucket_key, data_shapes, label_shapes,
                    shared=None)
        self._active_key = self._default_bucket_key

    def switch_bucket(self, bucket_key, data_shapes, label_shapes=None):
        """Make *bucket_key* active, binding its module on first use
        against the leader's parameter pool."""
        self._require_bound()
        if bucket_key not in self._by_key:
            self._spawn(bucket_key, data_shapes, label_shapes,
                        shared=self._leader)
        self._active_key = bucket_key
        if self.params_initialized and self._active is not self._leader:
            leader = self._leader
            mod = self._active
            mod._arg_params, mod._aux_params = (leader._arg_params,
                                                leader._aux_params)
            mod.params_initialized = True
            if getattr(mod, "_shares_device_params", False):
                # device arrays are ALIASED with the leader's: the switch
                # is free (the reference's shared-pool behavior,
                # bucketing_module.py:35-106)
                mod._params_dirty = leader._params_dirty
            else:
                # fallback (heterogeneous bucket graphs): refresh device
                # copies from the leader's host dicts — sync them down
                # first or the new bucket resumes from pre-update weights
                if leader._params_dirty:
                    leader._sync_params_from_devices()
                mod._exec_group.set_params(leader._arg_params,
                                           leader._aux_params)
        if self.optimizer_initialized and \
                not self._active.optimizer_initialized:
            self._lend_optimizer(self._active)

    # ---- optimizer ----

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        self._require_ready()
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring.")
            return
        self._active.init_optimizer(kvstore, optimizer, optimizer_params,
                                    force_init=force_init)
        self.optimizer_initialized = True

    def _lend_optimizer(self, mod):
        """Point *mod* at the leader's optimizer/kvstore/updater."""
        leader = self._leader
        mod._optimizer, mod._updater = leader._optimizer, leader._updater
        mod._kvstore = leader._kvstore
        mod._update_on_kvstore = leader._update_on_kvstore
        mod.optimizer_initialized = True

    # ---- computation ----

    def prepare(self, data_batch):
        self._require_ready()
        key = getattr(data_batch, "bucket_key", None)
        if key is not None:
            self.switch_bucket(key, data_batch.provide_data,
                               data_batch.provide_label)

    def forward(self, data_batch, is_train=None):
        self._require_ready()
        self._switch_for_batch(data_batch)
        self._active.forward(data_batch, is_train=is_train)

    def _switch_for_batch(self, data_batch):
        """Activate the batch's bucket (binding + optimizer-lending on
        first use happen inside switch_bucket)."""
        key = getattr(data_batch, "bucket_key", None)
        if key is not None and key != self._active_key:
            self.switch_bucket(key, data_batch.provide_data,
                               data_batch.provide_label)

    def _sync_active_to_leader(self):
        """Keep the leader authoritative for later bucket switches."""
        if self._active_key == self._default_bucket_key:
            return
        if getattr(self._active, "_shares_device_params", False):
            # aliased device arrays: the leader already sees the update;
            # only its host dicts are now stale
            self._leader._params_dirty = True
            return
        arg, aux = self._active.get_params()
        leader = self._leader
        leader._arg_params, leader._aux_params = arg, aux
        leader._exec_group.set_params(arg, aux)
        leader._params_dirty = False

    def _fit_step(self, data_batch):
        """Per-bucket fused step: switch to the batch's bucket, then one
        donated fwd+bwd+update program on that bucket's module (each
        bucket keeps its own compiled step).  Returns None whatever the
        active module returned: the next batch may switch the module that
        ``update_metric`` reads, so the fit loop settles each batch at
        once."""
        self._require_ready()
        self._switch_for_batch(data_batch)
        self._params_dirty = True
        self._active._fit_step(data_batch)
        self._sync_active_to_leader()

    def backward(self, out_grads=None):
        self._require_ready()
        self._active.backward(out_grads=out_grads)

    def update(self):
        self._require_ready()
        if not self.optimizer_initialized:
            raise AssertionError("init_optimizer must run before update")
        self._params_dirty = True
        self._active.update()
        self._sync_active_to_leader()

    def get_outputs(self, merge_multi_context=True):
        self._require_ready()
        return self._active.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        self._require_ready()
        if not self.inputs_need_grad:
            raise AssertionError("bind with inputs_need_grad=True first")
        return self._active.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._require_ready()
        self._active.update_metric(eval_metric, labels)

    def install_monitor(self, mon):
        self._require_bound()
        for mod in self._by_key.values():
            mod.install_monitor(mon)

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        self._leader.save_checkpoint(prefix, epoch, save_optimizer_states)
