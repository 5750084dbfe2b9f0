"""FusedExecutorGroup: multi-device Module as ONE SPMD program.

The reference's DataParallelExecutorGroup runs one executor per device and
reduces gradients through the kvstore afterwards
(``module/executor_group.py:233-430`` + ``comm.h``). TPU-native fast path:
bind a single executor whose data/label inputs are sharded over a
``Mesh(ctx_list, ("data",))`` and whose parameters are replicated — the
XLA SPMD partitioner splits the forward across devices and inserts the
gradient all-reduce itself, so forward+backward is one fused program and
the kvstore reduce disappears (there is one logical gradient already
summed over the global batch).

Numerics match the slow path exactly for stateless graphs: the fused
gradient equals the sum of per-device slice gradients the kvstore would
have produced. BatchNorm differs *by design*: the fused program computes
global (synchronised) batch statistics where per-device executors use
local slices — sync-BN semantics.

Enabled automatically for multi-device Module binds; opt out with
``MXNET_MODULE_FUSED=0``.
"""
from __future__ import annotations

import logging

import jax
from jax.sharding import PartitionSpec as P

from ..executor import Executor
from ..parallel import mesh as mesh_mod
from .. import ndarray as nd

__all__ = ["FusedExecutorGroup", "fused_enabled"]


def fused_enabled():
    import os
    return os.environ.get("MXNET_MODULE_FUSED", "1").strip().lower() \
        not in ("0", "false", "off", "no")


class _ShardedExecutor(Executor):
    """Executor whose inputs spread over a data-parallel mesh."""

    def __init__(self, symbol, ctx, mesh, batch_arg_names, **kwargs):
        self._mesh = mesh
        self._batch_args = set(batch_arg_names)
        self._data_sharding = mesh_mod.named_sharding(mesh, P("data"))
        self._replicated = mesh_mod.replicated(mesh)
        super().__init__(symbol, ctx, **kwargs)

    def _place(self, name, arr):
        sharding = self._data_sharding if name in self._batch_args \
            else self._replicated
        data = arr._data
        if getattr(data, "sharding", None) != sharding:
            data = jax.device_put(data, sharding)
            arr._set_data(data)
        return data

    def _place_rng(self, key):
        return jax.device_put(key, self._replicated)


class FusedExecutorGroup(object):
    """Drop-in executor-group with the DataParallelExecutorGroup surface,
    backed by one sharded executor (``num_device`` is 1: there is a single
    logical parameter/gradient copy)."""

    num_device = 1

    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=logging, fixed_param_names=None,
                 grad_req="write", state_names=None):
        self.param_names = list(param_names)
        self.batch_size = data_shapes[0].shape[0]
        if self.batch_size % len(contexts):
            raise ValueError(
                "fused group: batch size %d not divisible by %d devices"
                % (self.batch_size, len(contexts)))
        self._contexts = contexts
        devices = [c.jax_device for c in contexts]
        self._mesh = mesh_mod.make_mesh({"data": len(devices)}, devices)

        fixed = set(fixed_param_names or [])
        batch_args = [d.name for d in data_shapes] + \
            [d.name for d in (label_shapes or [])]
        self._label_names = [d.name for d in (label_shapes or [])]

        arg_dict, grad_dict = {}, {}
        shapes = {d.name: d.shape for d in data_shapes}
        shapes.update({d.name: d.shape for d in (label_shapes or [])})
        dtypes = {d.name: d.dtype
                  for d in list(data_shapes) + list(label_shapes or [])
                  if d.dtype is not None}
        arg_structs, _, aux_structs = symbol._infer(shape_kwargs=shapes,
                                                    dtype_kwargs=dtypes)
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()
        for name, st in zip(arg_names, arg_structs):
            shape = tuple(st.shape)
            arg_dict[name] = nd.zeros(shape, ctx=contexts[0], dtype=st.dtype)
            wants_grad = (for_training and name in self.param_names
                          and name not in fixed)
            if name in batch_args:
                wants_grad = for_training and inputs_need_grad
            if wants_grad and grad_req != "null":
                grad_dict[name] = nd.zeros(shape, ctx=contexts[0],
                                           dtype=st.dtype)
        aux_dict = {name: nd.zeros(tuple(st.shape), ctx=contexts[0],
                                   dtype=st.dtype)
                    for name, st in zip(aux_names, aux_structs)}

        req = {n: ("write" if n in grad_dict else "null")
               for n in arg_names}
        self._exec = _ShardedExecutor(
            symbol, contexts[0], self._mesh, batch_args,
            arg_dict=arg_dict, grad_dict=grad_dict, grad_req=req,
            aux_dict=aux_dict)
        self.execs = [self._exec]
        self._inputs_need_grad = inputs_need_grad
        self._data_names = [d.name for d in data_shapes]

        # one logical copy per param: the interface's per-device lists
        # degenerate to singletons
        self.param_arrays = [[arg_dict[n]] for n in self.param_names
                             if n in arg_dict]
        self.grad_arrays = [[grad_dict[n]] if n in grad_dict else [None]
                            for n in self.param_names]

    # ---- parameter movement ----

    def share_params_with(self, donor):
        """Alias the donor's sharded param/aux NDArrays (see
        DataParallelExecutorGroup.share_params_with — same zero-copy
        bucket-switch contract, single logical copy here)."""
        if type(donor) is not type(self):
            return False
        dex, mex = donor._exec, self._exec
        for names, attr in ((self.param_names, "arg_dict"),
                            (mex.aux_names, "aux_dict")):
            for name in names:
                src = getattr(dex, attr, {}).get(name)
                dst = getattr(mex, attr, {}).get(name)
                if src is None or dst is None or src.shape != dst.shape \
                        or src.dtype != dst.dtype:
                    return False
        for name in self.param_names:
            mex.arg_dict[name] = dex.arg_dict[name]
        for name in mex.aux_names:
            mex.aux_dict[name] = dex.aux_dict[name]
        self.param_arrays = [[mex.arg_dict[n]] for n in self.param_names
                             if n in mex.arg_dict]
        return True

    def place_params(self):
        """Replicate parameters and aux states over the mesh now.  The
        fused step's build calls it (``Module._get_cached_step``, under
        ``module_step_build``), so that the first step finds them where
        its program wants them and times tracing, compiling and running
        only; without the call the first ``_place`` of each does the same
        ``device_put``."""
        ex = self._exec
        for name in self.param_names:
            if name in ex.arg_dict:
                ex._place(name, ex.arg_dict[name])
        for name in ex.aux_names:
            ex._place(name, ex.aux_dict[name])

    def set_params(self, arg_params, aux_params, allow_extra=False):
        for name, arr in (arg_params or {}).items():
            if name in self._exec.arg_dict:
                arr.copyto(self._exec.arg_dict[name])
            elif not allow_extra:
                raise ValueError("unknown parameter %s" % name)
        for name, arr in (aux_params or {}).items():
            if name in self._exec.aux_dict:
                arr.copyto(self._exec.aux_dict[name])
            elif not allow_extra:
                raise ValueError("unknown aux state %s" % name)

    def get_params(self, arg_params, aux_params):
        for name, dst in arg_params.items():
            if name in self._exec.arg_dict:
                self._exec.arg_dict[name].copyto(dst)
        for name, dst in aux_params.items():
            if name in self._exec.aux_dict:
                self._exec.aux_dict[name].copyto(dst)

    # ---- computation ----

    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self._exec.grad_req and any(
                r != "null" for r in self._exec.grad_req.values())
        feed = dict(zip(self._data_names, data_batch.data))
        if data_batch.label:
            feed.update(zip(self._label_names, data_batch.label))
        self._exec.forward(is_train=bool(is_train), **feed)

    def forward_backward(self, data_batch):
        """Fused fwd+bwd in one SPMD program over the mesh."""
        feed = dict(zip(self._data_names, data_batch.data))
        if data_batch.label:
            feed.update(zip(self._label_names, data_batch.label))
        self._exec.forward_backward(**feed)

    def backward(self, out_grads=None):
        self._exec.backward(out_grads=out_grads)

    def get_outputs(self, merge_multi_context=True):
        outs = self._exec.outputs
        return outs if merge_multi_context else [[o] for o in outs]

    def get_input_grads(self, merge_multi_context=True):
        grads = [self._exec.grad_dict.get(n) for n in self._data_names]
        return grads if merge_multi_context else [[g] for g in grads]

    def update_metric(self, eval_metric, labels):
        eval_metric.update(labels, self._exec.outputs)

    def install_monitor(self, mon):
        mon.install(self._exec)
