"""Gluon Block / HybridBlock / SymbolBlock.

Parity surface: reference ``python/mxnet/gluon/block.py`` — ``Block`` (:33,
eager container + ``_BlockScope`` param management :120), ``HybridBlock``
(:305, ``hybridize`` traces ``hybrid_forward`` into a CachedOp
:364-417), ``SymbolBlock`` (:497).

TPU-native redesign: the reference's CachedOp (``src/imperative/
cached_op.cc``) builds an NNVM graph once and replays it through the
dependency engine.  Here hybridize compiles ``hybrid_forward`` into ONE XLA
program with ``jax.jit``: the traced function is pure
``(param_values, inputs, rng_key) -> (outputs, updated_aux)``; jax caches
specializations per input shape/dtype exactly like CachedOp's
shape-specialized plans (``cached_op.cc:175``).  Under ``autograd.record``
the whole jitted program lands on the tape as a single node via ``jax.vjp``
— the direct analogue of ``_CachedOp``'s fused backward
(``cached_op.cc:385``).
"""
from __future__ import annotations

import itertools as _itertools
import re
import threading

import numpy as np
import jax

from ..base import MXNetError
from ..context import Context, current_context
from .. import ndarray as nd
from ..ndarray.ndarray import NDArray, _wrap
from .. import symbol as _sym
from ..symbol import Symbol
from .. import autograd
from .. import random as _random
from .. import telemetry as _tel
from .parameter import Parameter, ParameterDict, DeferredInitializationError

__all__ = ["Block", "HybridBlock", "SymbolBlock"]


class _BlockScope(object):
    """Name-manager for Block construction (reference block.py:33)."""
    _current = threading.local()

    def __init__(self, block):
        self._block = block
        self._counter = {}
        self._old_scope = None

    @staticmethod
    def create(prefix, params, hint):
        """Create prefix and params for new Block."""
        current = getattr(_BlockScope._current, "value", None)
        if current is None:
            if prefix is None:
                from .. import name as _name
                prefix = _name.current().get(None, hint) + "_"
            if params is None:
                params = ParameterDict(prefix)
            else:
                params = ParameterDict(params.prefix, params)
            return prefix, params
        if prefix is None:
            count = current._counter.get(hint, 0)
            prefix = "%s%d_" % (hint, count)
            current._counter[hint] = count + 1
        if params is None:
            parent = current._block.params
            params = ParameterDict(parent.prefix + prefix, parent._shared)
        else:
            params = ParameterDict(params.prefix, params)
        return current._block.prefix + prefix, params

    def __enter__(self):
        self._old_scope = getattr(_BlockScope._current, "value", None)
        _BlockScope._current.value = self
        return self

    def __exit__(self, ptype, value, trace):
        _BlockScope._current.value = self._old_scope


def _flatten(args):
    """Flatten nested lists/tuples of NDArrays/Symbols; return flat list
    + fmt tree."""
    if args is None:
        return [], None
    if not isinstance(args, (list, tuple)):
        return [args], int(0)
    flat, fmts = [], []
    for a in args:
        f, fmt = _flatten(a)
        flat.extend(f)
        fmts.append(fmt)
    return flat, fmts


def _regroup(flat, fmt):
    if fmt is None:
        return None, flat
    if isinstance(fmt, int):
        return flat[0], flat[1:]
    ret = []
    for f in fmt:
        r, flat = _regroup(flat, f)
        ret.append(r)
    return ret, flat


class Block(object):
    """Base class for all neural network layers and models.

    Reference: ``gluon/block.py:33``.  Children assigned as attributes are
    registered automatically; ``collect_params`` walks the tree.
    """

    def __init__(self, prefix=None, params=None):
        self._empty_prefix = prefix == ""
        self._prefix, self._params = _BlockScope.create(
            prefix, params, self._alias())
        self._name = self._prefix[:-1] if self._prefix.endswith("_") \
            else self._prefix
        self._scope = _BlockScope(self)
        self._children = []

    def __repr__(self):
        s = "{name}(\n{modstr}\n)"
        modstr = "\n".join("  ({key}): {block}".format(
            key=i, block=_indent(repr(b), 2))
            for i, b in enumerate(self._children))
        return s.format(name=self.__class__.__name__, modstr=modstr)

    def __setattr__(self, name, value):
        if isinstance(value, Block):
            self.register_child(value)
        super(Block, self).__setattr__(name, value)

    def _alias(self):
        return self.__class__.__name__.lower()

    @property
    def prefix(self):
        return self._prefix

    @property
    def name(self):
        return self._name

    def name_scope(self):
        return self._scope

    @property
    def params(self):
        return self._params

    def collect_params(self):
        """Return a ParameterDict of this block's and children's params."""
        ret = ParameterDict(self._params.prefix)
        ret.update(self.params)
        for child in self._children:
            ret.update(child.collect_params())
        return ret

    def save_params(self, filename):
        self.collect_params().save(filename, strip_prefix=self.prefix)

    def load_params(self, filename, ctx=None, allow_missing=False,
                    ignore_extra=False):
        self.collect_params().load(filename, ctx, allow_missing,
                                   ignore_extra, self.prefix)

    def register_child(self, block):
        self._children.append(block)

    def initialize(self, init=None, ctx=None, verbose=False,
                   force_reinit=False):
        if init is None:
            from .. import initializer
            init = initializer.Uniform()
        self.collect_params().initialize(init, ctx, verbose,
                                         force_reinit=force_reinit)

    def hybridize(self, active=True):
        for child in self._children:
            child.hybridize(active)

    def cast(self, dtype):
        for child in self._children:
            child.cast(dtype)
        for _, param in self.params.items():
            param.cast(dtype)

    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, *args):
        raise NotImplementedError


def _indent(s_, num_spaces):
    lines = s_.split("\n")
    if len(lines) == 1:
        return s_
    first = lines.pop(0)
    return first + "\n" + "\n".join(" " * num_spaces + line
                                    for line in lines)


class HybridBlock(Block):
    """A Block that can be traced into one compiled XLA program.

    Reference: ``gluon/block.py:305``.  Subclasses implement
    ``hybrid_forward(F, x, *, weight=..., bias=...)`` written against
    ``F = mxnet_tpu.ndarray`` or ``F = mxnet_tpu.symbol``.
    """

    def __init__(self, prefix=None, params=None):
        super(HybridBlock, self).__init__(prefix, params)
        self._active = False
        self._cached_op = None
        self._reg_params = {}

    def __setattr__(self, name, value):
        super(HybridBlock, self).__setattr__(name, value)
        if isinstance(value, Parameter):
            assert name not in self._reg_params or \
                self._reg_params[name] is value, \
                "Overriding Parameter attribute %s is not allowed." % name
            self._reg_params[name] = value

    def register_child(self, block):
        if not isinstance(block, HybridBlock):
            raise ValueError(
                "Children of HybridBlock must also be HybridBlock, but %s "
                "has type %s." % (str(block), str(type(block))))
        super(HybridBlock, self).register_child(block)
        self._cached_op = None

    def hybridize(self, active=True):
        self._active = active
        self._cached_op = None
        super(HybridBlock, self).hybridize(active)

    def cast(self, dtype):
        self._cached_op = None
        super(HybridBlock, self).cast(dtype)

    # -- deferred shape inference -----------------------------------------
    def infer_shape(self, *args):
        """Infer deferred parameter shapes by symbolic tracing
        (reference block.py:417)."""
        params = {p.name: p for p in self.collect_params().values()}
        flat_args, in_fmt = _flatten(list(args))
        # the inputs' dtypes ride along: a cast() net (bf16 params) traced
        # against default-f32 data vars fails conv/dot dtype checks
        flat_vars = [_sym.var("data%d" % i, dtype=getattr(a, "dtype", None))
                     for i, a in enumerate(flat_args)]
        arg_tree, _ = _regroup(list(flat_vars), in_fmt)
        pkw = {name: p.var() for name, p in self._reg_params.items()}
        with autograd.pause():
            out = self.hybrid_forward(_sym, *arg_tree, **pkw)
        flat_out, _ = _flatten(out)
        out = flat_out[0] if len(flat_out) == 1 else _sym.Group(flat_out)
        shape_kw = {"data%d" % i: a.shape for i, a in enumerate(flat_args)}
        arg_shapes, _, aux_shapes = out.infer_shape_partial(**shape_kw)
        arg_names = out.list_arguments()
        aux_names = out.list_auxiliary_states()
        for name, shape in list(zip(arg_names, arg_shapes)) + \
                list(zip(aux_names, aux_shapes)):
            if name in params and shape is not None:
                params[name]._set_shape_if_deferred(shape)

    def _finish_deferred(self, *args):
        self.infer_shape(*args)
        for p in self.collect_params().values():
            p._finish_deferred_init()

    # -- execution ---------------------------------------------------------
    def __call__(self, *args):
        return self.forward(*args)

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            try:
                if self._active:
                    return self._call_cached_op(x, *args)
                params = {k: p.data() for k, p in self._reg_params.items()}
            except DeferredInitializationError:
                self._finish_deferred(x, *args)
                if self._active:
                    return self._call_cached_op(x, *args)
                params = {k: p.data() for k, p in self._reg_params.items()}
            return self.hybrid_forward(nd, x, *args, **params)
        if not isinstance(x, Symbol):
            raise ValueError(
                "HybridBlock input must be NDArray or Symbol, got %s"
                % type(x))
        pkw = {k: p.var() for k, p in self._reg_params.items()}
        return self.hybrid_forward(_sym, x, *args, **pkw)

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError

    # -- CachedOp (jit) path ----------------------------------------------
    def _build_cached_op(self):
        pd = self.collect_params()
        grad_params = [(n, p) for n, p in pd.items()
                       if p.grad_req != "null"]
        aux_params = [(n, p) for n, p in pd.items() if p.grad_req == "null"]
        self._cached_op = _CachedOp(self, [n for n, _ in grad_params],
                                    [n for n, _ in aux_params])
        self._cached_graph_params = (grad_params, aux_params)

    def _call_cached_op(self, *args):
        if self._cached_op is None:
            # trigger deferred init before tracing
            for p in self.collect_params().values():
                if p._deferred_init:
                    raise DeferredInitializationError(
                        "Parameter %s not initialized" % p.name)
                p._check_and_get()
            self._build_cached_op()
        return self._cached_op(*args)


_CACHED_OP_SEQ = _itertools.count()


class _CachedOp(object):
    """jit-compiled replay of a HybridBlock (reference cached_op.cc).

    The pure function is ``(grad_param_vals, aux_vals, input_vals, key)
    -> (flat_outputs, new_aux_vals)``; aux updates (BatchNorm moving
    stats) come back as explicit outputs and are written to the aux
    parameters after each call — the functional equivalent of the
    reference's in-place aux mutation.
    """

    def __init__(self, block, grad_names, aux_names):
        self._block = block
        self._grad_names = grad_names
        self._aux_names = aux_names
        pd = {p.name: p for p in block.collect_params().values()}
        self._pd = pd
        self._grad_params = [pd[n] for n in grad_names]
        self._aux_params = [pd[n] for n in aux_names]
        self._jit = {}   # train_mode -> jitted fn
        # watchdog identity: per-instance, so unrelated blocks (including
        # prefix="" ones) never aggregate into a phantom retrace storm
        self._watch_name = "gluon_cached_op:%s" % (
            block.prefix or "%s#%d" % (type(block).__name__,
                                       next(_CACHED_OP_SEQ)))
        self._fmt = None
        self._in_fmt = None

    def _pure(self, train_mode):
        block = self._block
        grad_names, aux_names = self._grad_names, self._aux_names

        def fn(grad_vals, aux_vals, in_vals, key):
            pd = self._pd
            handles = {}
            for name, v in list(zip(grad_names, grad_vals)) + \
                    list(zip(aux_names, aux_vals)):
                handles[name] = _wrap(v)
            saved = {}
            for name, h in handles.items():
                p = pd[name]
                saved[name] = p._data
                p._data = h
            try:
                with autograd.pause(train_mode=train_mode), \
                        _random.key_scope(key):
                    flat = [_wrap(v) for v in in_vals]
                    ins, _ = _regroup(list(flat), self._in_fmt)
                    out = block.hybrid_forward_dispatch(ins)
                    flat, fmt = _flatten(out)
                    self._fmt = fmt
                    out_vals = tuple(o._data for o in flat)
                    new_aux = tuple(handles[n]._data for n in aux_names)
            finally:
                for name, old in saved.items():
                    pd[name]._data = old
            return out_vals, new_aux
        return fn

    def _jitted(self, train):
        """The compiled replay program for *train* mode, built on first
        use (shared by ``__call__`` and the graftcheck AOT driver, so the
        trace tier analyzes the exact program this op ships)."""
        if train not in self._jit:
            pure = self._pure(train)
            from ..base import mirror_enabled
            if mirror_enabled():
                # MXNET_BACKWARD_DO_MIRROR (ref graph_executor.cc:281-304):
                # rematerialise forward activations in backward instead of
                # keeping them live — jax.checkpoint is the XLA-native form
                pure = jax.checkpoint(pure)
            self._jit[train] = _tel.watch_jit(jax.jit(pure),
                                              self._watch_name)
        return self._jit[train]

    def __call__(self, *args):
        grad_params = self._grad_params
        aux_params = self._aux_params
        grad_vals = tuple(p._data._data for p in grad_params)
        aux_vals = tuple(p._data._data for p in aux_params)
        flat_in, in_fmt = _flatten(list(args))
        self._in_fmt = in_fmt
        in_vals = tuple(x._data for x in flat_in)
        key = _random.next_key()
        train = autograd.is_training()
        recording = autograd.is_recording()

        jitted = self._jitted(train)

        if recording:
            def diff_fn(gvals, ivals):
                return jitted(gvals, aux_vals, ivals, key)
            (out_vals, new_aux), vjp_fn = jax.vjp(
                diff_fn, grad_vals, in_vals)

            def tape_vjp(out_grads):
                zeros_aux = tuple(jax.numpy.zeros_like(a) for a in new_aux)
                d_g, d_in = vjp_fn((tuple(out_grads), zeros_aux))
                return list(d_g) + list(d_in)
            inputs = [p._data for p in grad_params] + flat_in
            diff_idx = list(range(len(inputs)))
            outputs = [_wrap(v) for v in out_vals]
            node = autograd.TapeNode(None, {}, inputs, outputs, diff_idx,
                                     vjp_fn=tape_vjp)
            for o in outputs:
                o._tape_node = node
            autograd.append_node(node)
        else:
            out_vals, new_aux = jitted(grad_vals, aux_vals, in_vals, key)
            outputs = [_wrap(v) for v in out_vals]

        for p, v in zip(aux_params, new_aux):
            p._data._set_data(v)
        out, _ = _regroup(outputs, self._fmt)
        return out


def tracecheck_programs():
    """AOT specimens for graftcheck: the hybridized-block replay program
    (``gluon_cached_op``), built through the same ``_CachedOp._jitted``
    path ``__call__`` uses.  A tiny Dense block stands in; its weight
    buffers exist (initialize allocates) but the program is only traced,
    never executed."""
    from . import nn
    net = nn.Dense(8, in_units=16)
    net.initialize()
    net._build_cached_op()
    co = net._cached_op
    x = nd.zeros((4, 16))
    _flat, co._in_fmt = _flatten([x])
    jitted = co._jitted(False)
    grad_vals = tuple(p._data._data for p in co._grad_params)
    aux_vals = tuple(p._data._data for p in co._aux_params)
    key = _random.next_key()
    return [("gluon_cached_op", jitted,
             (grad_vals, aux_vals, (x._data,), key), {})]


def _hybrid_forward_dispatch(self, ins):
    params = {k: p.data() for k, p in self._reg_params.items()}
    ndin = ins
    # children called inside hybrid_forward go through their own forward();
    # inside a trace they take the eager path (params already concrete or
    # tracer-bound via the handle swap in _CachedOp._pure).
    return self.hybrid_forward(nd, *ndin, **params)


HybridBlock.hybrid_forward_dispatch = _hybrid_forward_dispatch


class SymbolBlock(HybridBlock):
    """Construct a block from a Symbol (reference block.py:497)."""

    def __init__(self, outputs, inputs, params=None):
        super(SymbolBlock, self).__init__(prefix=None, params=params)
        self._prefix = ""
        self._params = ParameterDict("", params)
        if isinstance(inputs, Symbol):
            inputs = [inputs]
        if isinstance(outputs, (list, tuple)) and len(outputs) == 1:
            outputs = outputs[0]
        if isinstance(outputs, (list, tuple)):
            out = _sym.Group(outputs)
        else:
            out = outputs
        input_names = set(i.name for i in inputs)
        for name in out.list_arguments():
            if name not in input_names:
                self.params.get(name, allow_deferred_init=True)
        for name in out.list_auxiliary_states():
            self.params.get(name, grad_req="null",
                            allow_deferred_init=True)
        self._out = out
        self._input_names = [i.name for i in inputs]

    def forward(self, x, *args):
        if isinstance(x, NDArray):
            arg_dict = {self._input_names[0]: x}
            for n, a in zip(self._input_names[1:], args):
                arg_dict[n] = a
            aux_dict = {}
            aux_names = set(self._out.list_auxiliary_states())
            for name, p in self.params.items():
                (aux_dict if name in aux_names else arg_dict)[name] = p.data()
            ex = self._out.bind(x.context, arg_dict, grad_req="null",
                                aux_states=aux_dict)
            outs = ex.forward(is_train=autograd.is_training())
            return outs[0] if len(outs) == 1 else outs
        raise NotImplementedError(
            "SymbolBlock symbolic forward not supported")

    def hybrid_forward(self, F, x, *args, **kwargs):
        raise NotImplementedError
