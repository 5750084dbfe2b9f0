"""Predict-only inference API.

Reference analogue: the amalgamation build's C predict API
(``include/mxnet/c_predict_api.h`` / ``src/c_api/c_predict_api.cc`` —
MXPredCreate / MXPredSetInput / MXPredForward / MXPredGetOutput): a
minimal deployment surface that loads a ``-symbol.json`` + ``.params``
checkpoint and runs forward passes, nothing else.

TPU-native: the whole graph compiles to one jitted XLA program at
``Predictor`` creation; repeated ``forward`` calls reuse it.

    pred = Predictor.load("model-prefix", epoch=3,
                          input_shapes={"data": (1, 3, 224, 224)})
    probs = pred.forward(data=batch)[0]
"""
from __future__ import annotations

import numpy as np

from . import ndarray as nd
from .base import MXNetError
from .context import current_context

__all__ = ["Predictor"]


class Predictor(object):
    """A bound inference-only executor over a saved checkpoint."""

    def __init__(self, symbol, arg_params, aux_params, input_shapes,
                 ctx=None):
        ctx = ctx or current_context()
        self._ctx = ctx
        self._input_names = list(input_shapes)
        # kept for the serving tier: bucket-padded AOT variants re-infer
        # batch-dependent arg shapes from the symbol (serving/program.py)
        self._symbol = symbol
        self._input_shapes = {n: tuple(s) for n, s in input_shapes.items()}
        args = {}
        shapes = dict(input_shapes)
        arg_shapes, _, aux_shapes = symbol.infer_shape(**shapes)
        for name, shape in zip(symbol.list_arguments(), arg_shapes):
            if name in input_shapes:
                args[name] = nd.zeros(input_shapes[name], ctx=ctx)
            elif name in arg_params:
                args[name] = arg_params[name].as_in_context(ctx)
            elif name.endswith("_label") and shape is not None:
                # loss-head labels (softmax_label etc., the reference's
                # `<head>_label` naming convention) are unused at
                # inference: zero-bind them like Module.predict does.
                # Anything else missing is a real checkpoint defect.
                args[name] = nd.zeros(shape, ctx=ctx)
            else:
                raise MXNetError("checkpoint is missing parameter %r" % name)
        auxs = {}
        for name, shape in zip(symbol.list_auxiliary_states(), aux_shapes):
            if name not in aux_params:
                raise MXNetError("checkpoint is missing aux state %r" % name)
            auxs[name] = aux_params[name].as_in_context(ctx)
        self._exe = symbol.bind(ctx, args, aux_states=auxs, grad_req="null")
        self.output_names = symbol.list_outputs()

    @classmethod
    def load(cls, prefix, epoch, input_shapes, ctx=None):
        """Build a predictor from ``prefix-symbol.json`` +
        ``prefix-{epoch:04d}.params`` (ref MXPredCreate)."""
        from .model import load_checkpoint
        symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
        return cls(symbol, arg_params, aux_params, input_shapes, ctx=ctx)

    def set_input(self, **inputs):
        """Load input arrays by name (ref MXPredSetInput)."""
        for name, value in inputs.items():
            if name not in self._input_names:
                raise MXNetError("unknown input %r (have %s)"
                                 % (name, self._input_names))
            arr = value if isinstance(value, nd.NDArray) \
                else nd.array(np.asarray(value, np.float32))
            arr.copyto(self._exe.arg_dict[name])

    def forward(self, **inputs):
        """Set inputs (optional) and run inference; returns the output
        list (ref MXPredForward + MXPredGetOutput)."""
        if inputs:
            self.set_input(**inputs)
        return self._exe.forward(is_train=False)

    def get_output(self, index=0):
        if self._exe.outputs is None:
            raise MXNetError("run forward() first")
        return self._exe.outputs[index]


def _tracecheck_predictor():
    """Specimen Predictor for graftcheck: a tiny MLP with a loss head, so
    the zero-bound ``*_label`` path is part of the traced program exactly
    as a real checkpoint binds it.  Params are zeros — nothing is
    executed, only shapes/dtypes matter."""
    from . import ndarray as nd_mod
    from . import symbol as S
    data = S.Variable("data")
    net = S.FullyConnected(data, num_hidden=8, name="pt_fc1")
    net = S.Activation(net, act_type="relu")
    net = S.FullyConnected(net, num_hidden=4, name="pt_fc2")
    net = S.SoftmaxOutput(net, name="softmax")
    input_shapes = {"data": (2, 16)}
    arg_shapes, _, aux_shapes = net.infer_shape(**input_shapes)
    arg_params = {
        name: nd_mod.zeros(shape)
        for name, shape in zip(net.list_arguments(), arg_shapes)
        if name not in input_shapes and not name.endswith("_label")}
    aux_params = {
        name: nd_mod.zeros(shape)
        for name, shape in zip(net.list_auxiliary_states(), aux_shapes)}
    return Predictor(net, arg_params, aux_params, input_shapes)


def tracecheck_programs():
    """AOT specimen for graftcheck: the predictor's eval program through
    the Predictor construction path (checkpoint-shaped params, zero-bound
    loss labels) — the one owned jit surface the executor specimens do
    not exercise."""
    import jax

    from . import random as _random
    pred = _tracecheck_predictor()
    ex = pred._exe
    key = _random.next_key()
    spec = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    arg_specs = [spec(ex.arg_dict[n]) for n in ex.arg_names]
    aux_specs = [spec(ex.aux_dict[n]) for n in ex.aux_names]
    key_spec = jax.ShapeDtypeStruct(key.shape, key.dtype)
    return [("predictor_forward", ex._eval_jit,
             (arg_specs, aux_specs, key_spec), {})]


class _EmbeddedPredictor(object):
    """Byte-oriented shim behind the native C predict API
    (``native/predict_api.cc`` — ref ``include/mxnet/c_predict_api.h``).

    The C side traffics only in raw buffers: inputs arrive as float32
    bytes, outputs leave as float32 bytes plus a shape tuple, so the
    embedding layer never needs the numpy C API.
    """

    def __init__(self, symbol_json, param_bytes, input_names, input_shapes,
                 dev_type=1, dev_id=0):
        from . import context, symbol as sym_mod
        from .model import split_saved_params
        from .ndarray import utils as nd_utils
        symbol = sym_mod.load_json(symbol_json)
        arg_params, aux_params = split_saved_params(
            nd_utils.load_from_bytes(param_bytes))
        # reference dev_type codes: 1 = cpu, 2 = gpu (here: accelerator);
        # an accelerator that is not attached raises, it is not the host
        ctx = context.tpu(dev_id) if dev_type >= 2 else context.cpu(dev_id)
        shapes = {n: tuple(int(x) for x in s)
                  for n, s in zip(input_names, input_shapes)}
        self._pred = Predictor(symbol, arg_params, aux_params, shapes,
                               ctx=ctx)
        self._shapes = shapes
        self._inputs = {}
        self._outputs = []

    def set_input(self, key, raw):
        if key not in self._shapes:
            raise MXNetError("unknown input %r" % key)
        arr = np.frombuffer(raw, dtype=np.float32).reshape(
            self._shapes[key]).copy()
        self._inputs[key] = arr

    def forward(self):
        outs = self._pred.forward(**self._inputs)
        self._outputs = [np.ascontiguousarray(o.asnumpy(),
                                              dtype=np.float32)
                         for o in outs]

    def num_outputs(self):
        return len(self._outputs)

    def get_output_shape(self, index):
        return tuple(int(s) for s in self._outputs[index].shape)

    def get_output_bytes(self, index):
        return self._outputs[index].tobytes()
