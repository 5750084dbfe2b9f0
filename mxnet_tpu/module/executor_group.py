"""DataParallelExecutorGroup (parity: reference module/executor_group.py:99-430).

Reference behavior kept: slice the batch across a context list, one executor
per context sharing the symbol, scatter data, forward/backward all, per-device
grad arrays for the kvstore to reduce.  On a single TPU chip this is one
executor; the mesh-sharded pjit fast path lives in parallel/ (SURVEY §2.5 maps
DataParallelExecutorGroup → batch-sharded pjit).
"""
from __future__ import annotations

import numpy as np

from ..base import MXNetError
from .. import context as ctx_mod
from .. import ndarray as nd
from ..io import DataDesc

__all__ = ["DataParallelExecutorGroup", "_split_input_slice"]


def _split_input_slice(batch_size, work_load_list):
    """Split batch into per-device slices (reference executor_group.py:_split)."""
    total = sum(work_load_list)
    if batch_size < len(work_load_list):
        raise ValueError("batch size cannot be smaller than number of devices")
    slices = []
    start = 0
    for i, w in enumerate(work_load_list):
        n = int(round(batch_size * w / total)) if i < len(work_load_list) - 1 \
            else batch_size - start
        slices.append(slice(start, start + n))
        start += n
    return slices


class DataParallelExecutorGroup:
    def __init__(self, symbol, contexts, workload, data_shapes, label_shapes,
                 param_names, for_training, inputs_need_grad,
                 shared_group=None, logger=None, fixed_param_names=None,
                 grad_req="write", state_names=None):
        self.symbol = symbol
        self.contexts = contexts
        self.num_device = len(contexts)
        self.workload = workload or [1] * len(contexts)
        self.param_names = param_names
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self.fixed_param_names = fixed_param_names or []
        self.state_names = state_names or []
        self.arg_names = symbol.list_arguments()
        self.aux_names = symbol.list_auxiliary_states()
        self.output_names = symbol.list_outputs()

        self.data_names = [x.name if isinstance(x, DataDesc) else x[0]
                           for x in data_shapes]
        self.label_names = [x.name if isinstance(x, DataDesc) else x[0]
                            for x in (label_shapes or [])]

        if isinstance(grad_req, str):
            self.grad_req = {}
            for name in self.arg_names:
                if name in self.param_names:
                    self.grad_req[name] = ("null" if name in self.fixed_param_names
                                           else grad_req)
                elif name in self.data_names:
                    self.grad_req[name] = grad_req if inputs_need_grad else "null"
                else:
                    self.grad_req[name] = "null"
        else:
            self.grad_req = dict(grad_req)
        if not for_training:
            self.grad_req = {k: "null" for k in self.arg_names}

        self.batch_size = (data_shapes[0].shape if isinstance(data_shapes[0], DataDesc)
                           else data_shapes[0][1])[0]
        self.slices = _split_input_slice(self.batch_size, self.workload)
        self.execs = []
        self._bind_execs(data_shapes, label_shapes)

    def _sliced_shape(self, shapes, i):
        out = {}
        for d in shapes or []:
            name, shape = (d.name, d.shape) if isinstance(d, DataDesc) else d
            sl = self.slices[i]
            out[name] = (sl.stop - sl.start,) + tuple(shape[1:])
        return out

    def _bind_execs(self, data_shapes, label_shapes):
        self.execs = []
        type_dict = {d.name: d.dtype
                     for d in list(data_shapes) + list(label_shapes or [])
                     if isinstance(d, DataDesc) and d.dtype is not None}
        for i, c in enumerate(self.contexts):
            shape_kwargs = self._sliced_shape(data_shapes, i)
            shape_kwargs.update(self._sliced_shape(label_shapes, i))
            ex = self.symbol.simple_bind(c, grad_req=self.grad_req,
                                         type_dict=type_dict,
                                         **shape_kwargs)
            self.execs.append(ex)
        self.data_arrays = [[e.arg_dict[n] for e in self.execs]
                            for n in self.data_names]
        self.label_arrays = [[e.arg_dict[n] for e in self.execs]
                             for n in self.label_names if n in self.arg_names]
        self.param_arrays = [[e.arg_dict[n] for e in self.execs]
                             for n in self.param_names]
        # grads aligned to param_names (None when fixed/no-grad)
        self.grad_arrays = []
        for n in self.param_names:
            if self.grad_req.get(n, "null") != "null":
                self.grad_arrays.append([e.grad_dict[n] for e in self.execs])
            else:
                self.grad_arrays.append(None)
        self.aux_arrays = [[e.aux_dict[n] for e in self.execs]
                           for n in self.aux_names]

    # -- params ------------------------------------------------------------
    def share_params_with(self, donor):
        """Alias the donor group's device-resident param/aux NDArrays.

        The TPU answer to the reference's shared memory pool
        (module/bucketing_module.py:35-106 + graph_executor.cc:868
        storage sharing): executors read ``handle._data`` at call time
        and every update path rebinds the handle in place, so aliasing
        the handles makes bucket switches zero-copy — no device→host
        sync, no host→device set_params. Returns True when every param
        and aux state was shared (caller may then skip set_params)."""
        if type(donor) is not type(self) or \
                len(self.execs) != len(donor.execs):
            return False
        for names, dicts in ((self.param_names, "arg_dict"),
                             (self.aux_names, "aux_dict")):
            for name in names:
                for mine, theirs in zip(self.execs, donor.execs):
                    src = getattr(theirs, dicts).get(name)
                    dst = getattr(mine, dicts).get(name)
                    if src is None or dst is None \
                            or src.shape != dst.shape \
                            or src.dtype != dst.dtype:
                        return False
        for name in self.param_names:
            for mine, theirs in zip(self.execs, donor.execs):
                mine.arg_dict[name] = theirs.arg_dict[name]
        for name in self.aux_names:
            for mine, theirs in zip(self.execs, donor.execs):
                mine.aux_dict[name] = theirs.aux_dict[name]
        # refresh the per-device views the module/kvstore paths iterate
        self.param_arrays = [[e.arg_dict[n] for e in self.execs]
                             for n in self.param_names]
        self.aux_arrays = [[e.aux_dict[n] for e in self.execs]
                           for n in self.aux_names]
        return True

    def set_params(self, arg_params, aux_params, allow_extra=False):
        for ex in self.execs:
            ex.copy_params_from(arg_params, aux_params,
                                allow_extra_params=allow_extra)

    def get_params(self, arg_params, aux_params):
        def mean(block):
            # one copy needs no host arithmetic (slow in bfloat16)
            if len(block) == 1:
                return block[0].asnumpy()
            return sum(w.asnumpy() for w in block) / len(block)

        for name, block in zip(self.param_names, self.param_arrays):
            arg_params[name] = nd.array(mean(block), dtype=block[0].dtype)
        for name, block in zip(self.aux_names, self.aux_arrays):
            aux_params[name] = nd.array(mean(block), dtype=block[0].dtype)

    # -- execution ---------------------------------------------------------
    def _load_data(self, batch):
        for name, arrs in zip(self.data_names, self.data_arrays):
            src = batch.data[self.data_names.index(name)]
            for sl, dst in zip(self.slices, arrs):
                dst._set_data(src[sl.start:sl.stop]._data.astype(dst.dtype)
                              if hasattr(src, "_data")
                              else nd.array(src[sl.start:sl.stop])._data)

    def _load_label(self, batch):
        if not batch.label:
            return
        for i, (name, arrs) in enumerate(zip(self.label_names,
                                             self.label_arrays)):
            src = batch.label[i]
            for sl, dst in zip(self.slices, arrs):
                dst._set_data(src[sl.start:sl.stop]._data.astype(dst.dtype)
                              if hasattr(src, "_data")
                              else nd.array(src[sl.start:sl.stop])._data)

    def forward(self, data_batch, is_train=None):
        if is_train is None:
            is_train = self.for_training
        self._load_data(data_batch)
        if self.label_arrays and data_batch.label:
            self._load_label(data_batch)
        for ex in self.execs:
            ex.forward(is_train=is_train)

    def forward_backward(self, data_batch):
        """One fused fwd+bwd XLA program per device (Module.fit hot path;
        ref RunOps pushes cached ops only, graph_executor.cc:1403)."""
        assert self.for_training, \
            "re-bind with for_training=True to run backward"
        self._load_data(data_batch)
        if self.label_arrays and data_batch.label:
            self._load_label(data_batch)
        for ex in self.execs:
            ex.forward_backward()

    def backward(self, out_grads=None):
        assert self.for_training, "re-bind with for_training=True to run backward"
        for i, ex in enumerate(self.execs):
            if out_grads is None:
                ex.backward()
            else:
                sliced = [og[self.slices[i].start:self.slices[i].stop]
                          for og in out_grads]
                ex.backward(sliced)

    def get_outputs(self, merge_multi_context=True):
        outputs = [[ex.outputs[i] for ex in self.execs]
                   for i in range(len(self.execs[0].outputs))]
        if merge_multi_context:
            merged = []
            for per_dev in outputs:
                if len(per_dev) == 1:
                    merged.append(per_dev[0])
                else:
                    merged.append(nd.concatenate(per_dev, axis=0))
            return merged
        return outputs

    def get_input_grads(self, merge_multi_context=True):
        assert self.inputs_need_grad
        grads = [[ex.grad_dict[n] for ex in self.execs]
                 for n in self.data_names]
        if merge_multi_context:
            return [g[0] if len(g) == 1 else nd.concatenate(g, axis=0)
                    for g in grads]
        return grads

    def update_metric(self, eval_metric, labels):
        for i, ex in enumerate(self.execs):
            labels_slice = [l[self.slices[i].start:self.slices[i].stop]
                            for l in labels]
            eval_metric.update(labels_slice, ex.outputs)

    def install_monitor(self, mon):
        for ex in self.execs:
            ex.set_monitor_callback(mon.stat_helper if hasattr(mon, "stat_helper")
                                    else mon)
