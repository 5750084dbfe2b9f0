"""Module: the symbol + context-list training unit.

API parity with the reference ``python/mxnet/module/module.py:39`` (bind /
init_params / init_optimizer / forward / backward / update / checkpointing
incl. optimizer state), built independently around a DataParallelExecutorGroup
and the kvstore helpers in ``model.py``.
"""
from __future__ import annotations

import contextlib
import logging
import os
import warnings

import numpy as np

from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..initializer import Uniform, InitDesc
from ..io import DataDesc
from .. import telemetry as _tel
from ..model import (_create_kvstore, _initialize_kvstore, _update_params,
                     _update_params_on_kvstore, load_checkpoint)
from .base_module import BaseModule, _check_input_names
from .executor_group import DataParallelExecutorGroup

__all__ = ["Module"]


def _as_descs(shapes):
    """Normalise a list of (name, shape) tuples / DataDesc into DataDesc."""
    if shapes is None:
        return None
    return [s if isinstance(s, DataDesc) else DataDesc(*s) for s in shapes]


class Module(BaseModule):
    """Intermediate-level module over one symbol replicated on a ctx list."""

    def __init__(self, symbol, data_names=("data",),
                 label_names=("softmax_label",), logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None):
        super().__init__(logger=logger)

        ctxs = context if context is not None else ctx_mod.current_context()
        if isinstance(ctxs, ctx_mod.Context):
            ctxs = [ctxs]
        self._context = ctxs
        self._work_load_list = work_load_list or [1] * len(ctxs)
        if len(self._work_load_list) != len(ctxs):
            raise ValueError("work_load_list must have one entry per context")

        self._symbol = symbol
        self._partition_names(symbol, data_names, label_names,
                              fixed_param_names, state_names)
        _check_input_names(symbol, self._data_names, "data", True)

        # Host-side canonical parameter copies; device copies live in the
        # executor group and are flagged dirty after each update().
        self._arg_params = self._aux_params = None
        self._params_dirty = False
        self._optimizer = self._updater = self._kvstore = None
        self._update_on_kvstore = self._preload_opt_states = None
        self._exec_group = self._data_shapes = self._label_shapes = None

    def _partition_names(self, symbol, data_names, label_names,
                         fixed_param_names, state_names):
        """Split symbol arguments into data / label / parameter groups."""
        data_names = list(data_names or [])
        label_names = list(label_names or [])
        args = symbol.list_arguments()
        inputs = set(data_names) | set(label_names)
        self._data_names = data_names
        self._label_names = [n for n in label_names if n in args]
        self._param_names = [a for a in args if a not in inputs]
        self._fixed_param_names = list(fixed_param_names or [])
        self._state_names = list(state_names or [])
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

    # ---- checkpointing ----

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        """Rebuild a Module from ``prefix-symbol.json`` + ``prefix-NNNN.params``."""
        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(sym, **kwargs)
        mod._arg_params, mod._aux_params = args, auxs
        mod.params_initialized = True
        if load_optimizer_states:
            mod._preload_opt_states = "%s-%04d.states" % (prefix, epoch)
        return mod  # optimizer states attach lazily at init_optimizer

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False):
        """Write symbol json + params (+ optimizer states) for *epoch*."""
        self._symbol.save(prefix + "-symbol.json")
        params_file = "%s-%04d.params" % (prefix, epoch)
        self.save_params(params_file)
        logging.info('Saved checkpoint to "%s"', params_file)
        if save_optimizer_states:
            states_file = "%s-%04d.states" % (prefix, epoch)
            self.save_optimizer_states(states_file)
            logging.info('Saved optimizer state to "%s"', states_file)

    def save_optimizer_states(self, fname):
        if not self.optimizer_initialized:
            raise AssertionError("optimizer not initialized")
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as fh:
                fh.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        if not self.optimizer_initialized:
            raise AssertionError("optimizer not initialized")
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as fh:
                self._updater.set_states(fh.read())

    # ---- properties ----

    output_names = property(lambda self: self._output_names)
    data_names = property(lambda self: self._data_names)
    label_names = property(lambda self: self._label_names)

    @property
    def data_shapes(self):
        self._require_bound()
        return self._data_shapes

    @property
    def label_shapes(self):
        self._require_bound()
        return self._label_shapes

    @property
    def output_shapes(self):
        self._require_bound()
        execs = self._exec_group.execs
        try:
            outs = execs[0].outputs if execs else []
            return list(zip(self._output_names, (o.shape for o in outs)))
        except Exception:
            # before the first forward: infer symbolically from the bound
            # input shapes (the reference caches these at bind time)
            shapes = {d.name: d.shape for d in self._data_shapes}
            if self._label_shapes:
                shapes.update({d.name: d.shape for d in self._label_shapes})
            _, out_shapes, _ = self._symbol.infer_shape(**shapes)
            return list(zip(self._output_names, out_shapes))

    def _require_bound(self):
        if not self.binded:
            raise AssertionError("module is not bound")

    def _shape_key(self):
        """Cache key for the exec-group-per-shape-signature cache."""
        req = getattr(self, "_grad_req", "write")
        if isinstance(req, dict):
            req = tuple(sorted(req.items()))
        elif isinstance(req, (list, tuple)):
            req = tuple(req)
        # dtype is part of a group's identity: _bind_execs passes type_dict
        # into simple_bind, so same-shape/different-dtype must not collide
        def _dt(d):
            dt = getattr(d, "dtype", None)
            try:                       # canonical spelling: np.float32 and
                return str(np.dtype(dt))  # "float32" must hit the same key
            except TypeError:
                return str(dt)

        return (tuple((d.name, tuple(d.shape), _dt(d))
                      for d in self._data_shapes),
                tuple((d.name, tuple(d.shape), _dt(d))
                      for d in (self._label_shapes or ())),
                self.for_training, self.inputs_need_grad, req)

    # ---- parameters ----

    def get_params(self):
        self._require_ready()
        if self._params_dirty:
            self._sync_params_from_devices()
        return self._arg_params, self._aux_params

    def _alloc_host_params(self):
        """Allocate zeroed host-side copies shaped like executor 0's arrays."""
        proto = self._exec_group.execs[0]
        if self._arg_params is None:
            self._arg_params = {
                n: nd.zeros(proto.arg_dict[n].shape,
                            dtype=proto.arg_dict[n].dtype)
                for n in self._param_names}
        if self._aux_params is None:
            self._aux_params = {
                n: nd.zeros(proto.aux_dict[n].shape,
                            dtype=proto.aux_dict[n].dtype)
                for n in self._aux_names}

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        """Fill parameters from *arg_params*/*aux_params* or *initializer*.

        Contract (ref module.py:246): provided dicts win; missing entries fall
        back to the initializer when ``allow_missing``, else raise.
        """
        if not self._may_init_params(force_init):
            return
        with _tel.span("module_init_params", cat="setup",
                       args=self._setup_args()):
            with _tel.span("init_params_host", cat="setup"):
                self._fill_host_params(initializer, arg_params, aux_params,
                                       allow_missing)
            with _tel.span("init_params_place", cat="setup"):
                self._place_host_params(allow_extra)

    def _may_init_params(self, force_init):
        if self.params_initialized and not force_init:
            warnings.warn("init_params ignored: already initialized "
                          "(pass force_init=True to override)", stacklevel=3)
            return False
        self._require_bound()
        return True

    def _fill_host_params(self, initializer, arg_params, aux_params,
                          allow_missing):
        if initializer is None:
            initializer = Uniform(0.01)
        self._alloc_host_params()
        attrs = self._symbol.attr_dict()

        for target, source in ((self._arg_params, arg_params),
                               (self._aux_params, aux_params)):
            for name in sorted(target):
                desc = InitDesc(name, attrs.get(name))
                arr = target[name]
                if source is None:
                    initializer(desc, arr)
                elif name in source:
                    if source[name] is not arr:
                        source[name].copyto(arr)
                elif allow_missing:
                    if initializer is not None:
                        initializer(desc, arr)
                else:
                    raise RuntimeError("%s is not presented" % name)

    def _place_host_params(self, allow_extra):
        self.params_initialized, self._params_dirty = True, False
        self._exec_group.set_params(
            self._arg_params, self._aux_params, allow_extra=allow_extra)

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        if not allow_missing:
            # init_params' work without its set-up spans: fit() comes here
            # at every epoch's end, which is no start
            if self._may_init_params(force_init):
                self._fill_host_params(None, arg_params, aux_params, False)
                self._place_host_params(allow_extra)
            return
        if self.params_initialized and not force_init:
            warnings.warn("set_params ignored: already initialized "
                          "(pass force_init=True to override)", stacklevel=2)
            return
        # Partial update: push straight to devices, host copies become stale.
        self._exec_group.set_params(arg_params, aux_params,
                                    allow_extra=allow_extra)
        self._params_dirty, self.params_initialized = True, True

    def _sync_params_from_devices(self):
        self._exec_group.get_params(self._arg_params, self._aux_params)
        self._params_dirty = False

    # ---- binding ----

    def bind(self, data_shapes, label_shapes=None,
             for_training=True, inputs_need_grad=False, force_rebind=False,
             shared_module=None, grad_req="write"):
        """Create device executors for the given input shapes."""
        if force_rebind:
            self.binded, self._exec_group = False, None
            self._data_shapes = self._label_shapes = None
            self.__dict__.pop("_reshape_cache", None)
        if self.binded:
            self.logger.warning("Already bound, ignoring bind()")
            return
        with _tel.span("module_bind", cat="setup",
                       args=dict(self._setup_args(),
                                 contexts=len(self._context),
                                 for_training=bool(for_training))):
            self._bind(data_shapes, label_shapes, for_training,
                       inputs_need_grad, shared_module, grad_req)

    def _setup_args(self):
        """What every set-up span of this module carries: which module it
        is, so that a reader can tell one trainer's start from another's
        in the same process."""
        return {"module": id(self)}

    def _bind(self, data_shapes, label_shapes, for_training,
              inputs_need_grad, shared_module, grad_req):
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req
        self._data_shapes = _as_descs(data_shapes)
        self._label_shapes = _as_descs(label_shapes)
        self._exec_group = self._make_exec_group(for_training,
                                                 inputs_need_grad, grad_req)
        self.binded = True
        self.__dict__.setdefault("_reshape_cache", {})[
            self._shape_key()] = self._exec_group

        self._shares_device_params = False
        if shared_module is not None:
            # Alias (not copy) the donor module's host params, per reference.
            self._arg_params, self._aux_params = (
                shared_module._arg_params, shared_module._aux_params)
            self.params_initialized = True
            donor_group = getattr(shared_module, "_exec_group", None)
            if donor_group is not None:
                # alias the donor's DEVICE arrays too: bucket switches
                # then cost nothing (no sync-down, no set_params up)
                self._shares_device_params = \
                    self._exec_group.share_params_with(donor_group)
                if self._shares_device_params:
                    self._params_dirty = shared_module._params_dirty
        if self.params_initialized and not self._shares_device_params:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    def _make_exec_group(self, for_training, inputs_need_grad,
                         grad_req="write"):
        group_cls = DataParallelExecutorGroup
        if len(self._context) > 1:
            from .fused_group import FusedExecutorGroup, fused_enabled
            same_kind = len({c.device_type for c in self._context}) == 1
            batch = self._data_shapes[0].shape[0]
            if fused_enabled() and same_kind                     and batch % len(self._context) == 0:
                # one SPMD program over a device mesh instead of per-device
                # executors + kvstore reduce (the TPU-native fast path)
                group_cls = FusedExecutorGroup
        return group_cls(
            self._symbol, self._context, self._work_load_list,
            self._data_shapes, self._label_shapes, self._param_names,
            for_training, inputs_need_grad, shared_group=None,
            logger=self.logger, fixed_param_names=self._fixed_param_names,
            grad_req=grad_req, state_names=self._state_names)

    def reshape(self, data_shapes, label_shapes=None):
        """Rebind executors for new input shapes, keeping parameters.

        Exec groups are cached per shape signature (the reference reuses
        the shared memory pool, executor.py reshape; under XLA the costly
        resource is the compiled program, so what we keep is the bound
        group with its jit caches). Alternating shapes — bucketing, the
        last partial batch of every epoch — rebind at zero cost after
        the first visit."""
        self._require_bound()
        self._data_shapes = _as_descs(data_shapes)
        self._label_shapes = _as_descs(label_shapes)
        cache = self.__dict__.setdefault("_reshape_cache", {})
        key = self._shape_key()
        group = cache.pop(key, None)   # pop+reinsert = LRU ordering
        if group is None:
            group = self._make_exec_group(
                self.for_training, self.inputs_need_grad,
                grad_req=getattr(self, "_grad_req", "write"))
            # bound the cache: each entry pins compiled programs AND a
            # device-resident parameter copy — many distinct shapes
            # (e.g. free-form inference batches) must not accumulate
            # deliberate re-read: reshape is a rebind (rare), and tests
            # monkeypatch the limit at runtime
            # graftlint: disable=JG006
            limit = int(os.environ.get("MXNET_MODULE_RESHAPE_CACHE", "8"))
            while len(cache) >= max(limit, 1):
                evicted_key = next(iter(cache))
                cache.pop(evicted_key)
        cache[key] = group
        self._exec_group = group
        if self.params_initialized:
            self._exec_group.set_params(self._arg_params, self._aux_params)

    # ---- optimizer ----

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        """Create kvstore + optimizer; decide update-on-kvstore placement."""
        self._require_ready()
        if self.optimizer_initialized and not force_init:
            self.logger.warning("optimizer already initialized, ignoring...")
            return
        with _tel.span("module_init_optimizer", cat="setup",
                       args=self._setup_args()):
            self._init_optimizer(kvstore, optimizer, optimizer_params)

    def _init_optimizer(self, kvstore, optimizer, optimizer_params):
        if self._params_dirty:
            self._sync_params_from_devices()

        # the fused SPMD group holds ONE logical param/grad copy: the
        # gradient is already globally reduced inside the XLA program, so
        # a single-device kvstore decision applies
        n_dev = getattr(self._exec_group, "num_device", len(self._context))
        kvstore, update_on_kvstore = _create_kvstore(
            kvstore, n_dev, self._arg_params)

        effective_batch = self._exec_group.batch_size
        if kvstore and "dist" in kvstore.type and "_async" in kvstore.type:
            effective_batch *= kvstore.num_workers

        if isinstance(optimizer, str):
            optimizer = self._build_optimizer(optimizer, optimizer_params,
                                              update_on_kvstore,
                                              1.0 / effective_batch)
        elif not isinstance(optimizer, opt.Optimizer):
            raise TypeError("optimizer must be a name or an Optimizer")

        self._optimizer, self._kvstore = optimizer, kvstore
        self._update_on_kvstore = update_on_kvstore
        self._cached_step, self._cached_step_unusable = None, False

        if kvstore:
            _initialize_kvstore(
                kvstore=kvstore, param_arrays=self._exec_group.param_arrays,
                arg_params=self._arg_params, param_names=self._param_names,
                update_on_kvstore=update_on_kvstore)
        if update_on_kvstore:
            self._updater = None
            kvstore.set_optimizer(optimizer)
        else:
            self._updater = opt.get_updater(optimizer)
        self.optimizer_initialized = True

        if self._preload_opt_states:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    def _build_optimizer(self, name, optimizer_params, update_on_kvstore,
                         rescale_grad):
        """Instantiate a named optimizer with the per-slot name mapping the
        Updater uses for lr/wd multipliers."""
        n_dev = getattr(self._exec_group, "num_device", len(self._context))
        idx2name = {}
        for i, pname in enumerate(self._exec_group.param_names):
            if update_on_kvstore:
                idx2name[i] = pname
            else:
                for k in range(n_dev):
                    idx2name[i * n_dev + k] = pname
        kwargs = dict(optimizer_params)
        kwargs.setdefault("rescale_grad", rescale_grad)
        return opt.create(name, sym=self.symbol, param_idx2name=idx2name,
                          **kwargs)

    # ---- computation ----

    def forward(self, data_batch, is_train=None):
        self._require_ready()
        self._maybe_reshape(data_batch)
        self._exec_group.forward(data_batch, is_train)

    def _maybe_reshape(self, data_batch):
        """Rebind when the incoming batch's shapes differ from the bound ones
        (last partial batch, bucketing); preserves trained params."""
        bound = tuple(d.shape for d in self._data_shapes)
        incoming = tuple(x.shape for x in data_batch.data)
        if bound == incoming:
            return
        if self._params_dirty and self.params_initialized:
            self._sync_params_from_devices()
        if getattr(data_batch, "provide_data", None):
            new_data = data_batch.provide_data
        else:
            new_data = [DataDesc(d.name, shp, d.dtype, d.layout)
                        for d, shp in zip(self._data_shapes, incoming)]
        if getattr(data_batch, "provide_label", None):
            new_label = data_batch.provide_label
        elif getattr(data_batch, "label", None):
            new_label = [DataDesc(d.name, arr.shape, d.dtype, d.layout)
                         for d, arr in zip(self._label_shapes,
                                           data_batch.label)]
        else:
            new_label = None
        self.reshape(new_data, new_label)

    def _fit_step(self, data_batch):
        """fit-loop step. Fast path: fwd+bwd+optimizer as ONE donated
        compiled program (cached_step.CachedTrainStep) when the update
        placement allows — single logical param copy, optimizer on
        worker. Falls back to forward_backward + update otherwise.

        The fused program's outputs are new handles every step and none
        of its donated inputs, so they are handed back, with the group
        that bound them (a later batch of another shape rebinds): the
        fit loop may enqueue the next step before it reads this one's
        metric (BaseModule._fit_step).  The fallback, whose outputs stay
        in its executors (one a device under a kvstore), returns None."""
        self._maybe_reshape(data_batch)
        step = self._get_cached_step()
        if step is not None:
            feed = dict(zip(self._data_names, data_batch.data))
            if data_batch.label:
                feed.update(zip(self._label_names, data_batch.label))
            try:
                outputs = step.run(feed)
                self._params_dirty = True
                return self._exec_group, outputs
            except NotImplementedError:
                # optimizer has no pure update_step: permanently fall back
                self._cached_step_unusable = True
                self._cached_step = None
        super()._fit_step(data_batch)

    @contextlib.contextmanager
    def _outputs_read_as(self, held):
        # every reader (the groups' update_metric, which slices the labels
        # as its own batch was split, get_outputs, a subclass's own) goes
        # through the bound group and its executor's output list
        group, outputs = held
        ex = group.execs[0]
        newest = self._exec_group, ex._outputs
        self._exec_group, ex._outputs = group, outputs
        try:
            yield
        finally:
            self._exec_group, ex._outputs = newest

    def _get_cached_step(self):
        from .cached_step import CachedTrainStep, fused_step_enabled
        if getattr(self, "_cached_step_unusable", False) \
                or not fused_step_enabled():
            return None
        if not (self.optimizer_initialized and self._updater is not None
                and self._kvstore is None and not self.inputs_need_grad):
            return None
        group = self._exec_group
        if len(group.execs) != 1:
            return None
        ex = group.execs[0]
        if ex._group2ctx or ex._monitor is not None:
            return None
        if any(r not in ("write", "null") for r in ex.grad_req.values()):
            return None
        # cache on the exec group so alternating reshape() shapes (their
        # groups are themselves cached) keep their compiled step programs
        cached = getattr(group, "_cached_train_step", None)
        if cached is not None and cached._exec is ex \
                and cached._updater is self._updater:
            self._cached_step = cached
            return cached
        with _tel.span("module_step_build", cat="setup",
                       args=self._setup_args()):
            try:
                cached = CachedTrainStep(ex, self._updater,
                                         group.param_names)
            except ValueError:
                cached = None
                self._cached_step_unusable = True
            if cached is not None:
                cached.setup_args = self._setup_args()
                place = getattr(group, "place_params", None)
                if place is not None:
                    place()     # the SPMD group: over the mesh, once
        group._cached_train_step = cached
        self._cached_step = cached
        return cached

    def forward_backward(self, data_batch):
        """fwd+bwd as one compiled program per executor (falls back to the
        two-call path when the group doesn't support fusing)."""
        self._require_ready()
        self._maybe_reshape(data_batch)
        fused = getattr(self._exec_group, "forward_backward", None)
        if fused is not None:
            fused(data_batch)
        else:
            self._exec_group.forward(data_batch, True)
            self._exec_group.backward()

    def backward(self, out_grads=None):
        self._require_ready()
        self._exec_group.backward(out_grads=out_grads)

    def update(self):
        """Apply the optimizer to accumulated gradients (ref module.py:615)."""
        if not self.optimizer_initialized:
            raise AssertionError("init_optimizer must run before update")
        self._require_ready()
        self._params_dirty = True
        group = self._exec_group
        if self._update_on_kvstore:
            _update_params_on_kvstore(group.param_arrays, group.grad_arrays,
                                      self._kvstore, group.param_names)
        else:
            _update_params(group.param_arrays, group.grad_arrays,
                           updater=self._updater, kvstore=self._kvstore,
                           num_device=getattr(group, "num_device",
                                              len(self._context)),
                           param_names=group.param_names)

    def get_outputs(self, merge_multi_context=True):
        self._require_ready()
        return self._exec_group.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        self._require_ready()
        if not self.inputs_need_grad:
            raise AssertionError("bind with inputs_need_grad=True first")
        return self._exec_group.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels):
        self._exec_group.update_metric(eval_metric, labels)

    def install_monitor(self, mon):
        self._require_bound()
        self._exec_group.install_monitor(mon)

    def prepare(self, data_batch):
        pass
