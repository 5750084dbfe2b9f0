"""Weight initializers.

API parity with the reference ``python/mxnet/initializer.py:34-676``
(InitDesc, pattern-dispatch Initializer protocol, the Zero…FusedRNN zoo,
Load/Mixed). Independent design: name-suffix dispatch is table-driven, and
structured initializers (Bilinear) are vectorised numpy rather than loops.
"""
from __future__ import annotations

import json
import re

import numpy as np

from .base import Registry, MXNetError
from . import ndarray as nd
from . import random as _random
from .ops.registry import parse_attr_string

__all__ = ["InitDesc", "Initializer", "register", "create", "Zero", "One",
           "Constant", "Uniform", "StateSpaceInit", "Normal", "Orthogonal",
           "Xavier",
           "MSRAPrelu", "Bilinear", "LSTMBias", "Load", "Mixed", "init"]

_REG = Registry("initializer")


class InitDesc(str):
    """Parameter name enriched with symbol attrs + the global initializer."""

    def __new__(cls, name, attrs=None, global_init=None):
        self = super().__new__(cls, name)
        self.attrs = attrs or {}
        self.global_init = global_init
        return self


# (name suffix → handler method) dispatch table, checked in order.
_SUFFIX_DISPATCH = (
    (("weight",), "_init_weight"),
    (("bias",), "_init_bias"),
    (("gamma",), "_init_gamma"),
    (("beta",), "_init_beta"),
    (("moving_mean", "running_mean", "moving_inv_var", "moving_avg",
      "min", "max"), "_init_zero"),
    (("moving_var", "running_var"), "_init_one"),
)


class Initializer:
    """Base initializer implementing the reference dispatch protocol:
    an ``__init__`` attr on the variable wins, else the name suffix picks
    the handler (weight/bias/gamma/beta/aux-stat)."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs
        self._verbose, self._print_func = False, None

    def set_verbosity(self, verbose=False, print_func=None):
        self._verbose, self._print_func = verbose, print_func
        return self

    def dumps(self):
        return json.dumps([type(self).__name__.lower(), self._kwargs])

    def __call__(self, desc, arr):
        if not isinstance(desc, InitDesc):
            desc = InitDesc(desc)
        if desc.global_init is None:
            desc.global_init = self
        attr_init = desc.attrs.get("__init__", "")
        if attr_init:
            # variable-level override: serialized [class, kwargs] or a plain
            # registered name — the reference accepts both via create(init)
            # (ref python/mxnet/initializer.py:134).
            create(attr_init)._init_weight(desc, arr)
            return
        lowered = desc.lower()
        for suffixes, handler in _SUFFIX_DISPATCH:
            if lowered.endswith(suffixes):
                getattr(self, handler)(desc, arr)
                return
        self._init_default(desc, arr)

    def _init_zero(self, _, arr):
        arr[:] = 0.0

    def _init_one(self, _, arr):
        arr[:] = 1.0

    _init_bias = _init_zero
    _init_beta = _init_zero
    _init_gamma = _init_one

    def _init_weight(self, name, arr):
        raise NotImplementedError()

    def _init_default(self, name, arr):
        raise ValueError(
            'Unknown initialization pattern for %s. Default initialization '
            'is now limited to "weight", "bias", "gamma", and "beta". '
            'Please use mx.sym.Variable(init=mx.init.*) to set the '
            'initialization pattern' % name)

    def __eq__(self, other):
        return (type(self) is type(other)
                and self._kwargs == other._kwargs)

    __hash__ = object.__hash__


def register(klass):
    _REG.register(klass, klass.__name__)
    return klass


def create(name, **kwargs):
    """Name, JSON ``[class, kwargs]`` string, or instance → Initializer
    (name-or-JSON acceptance mirrors ref python/mxnet/initializer.py:134)."""
    if isinstance(name, Initializer) or callable(name) and not isinstance(name, (str, type)):
        return name
    if isinstance(name, str) and name.lstrip().startswith("["):
        cls_name, cls_kwargs = json.loads(name)
        return _REG.get(cls_name)(**cls_kwargs)
    return _REG.get(name)(**kwargs)


@register
class Zero(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 0.0
    _init_default = _init_weight


@register
class One(Initializer):
    def _init_weight(self, _, arr):
        arr[:] = 1.0
    _init_default = _init_weight


_REG.register(Zero, "zeros")
_REG.register(One, "ones")


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, _, arr):
        arr[:] = self.value
    _init_default = _init_weight


@register
class Uniform(Initializer):
    """U(-scale, scale)."""

    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, _, arr):
        arr[:] = _random.host_rng().uniform(-self.scale, self.scale,
                                            arr.shape)


@register
class StateSpaceInit(Initializer):
    """Mamba-2's published initial range of a state-space scan's
    parameters (Dao and Gu, arXiv:2405.21060, and its reference module's
    defaults): ``a_log`` is the log of A drawn uniformly in [low, high]
    (1, 16 there), ``dt_bias`` is softplus^-1 of a step drawn
    log-uniformly in [low, high] (0.001, 0.1 there), so that a token's
    decay exp(-A dt) spans ~0.2 to ~0.999 over the heads."""

    def __init__(self, param="a_log", low=1.0, high=16.0):
        super().__init__(param=param, low=low, high=high)
        if param not in ("a_log", "dt_bias"):
            raise ValueError("StateSpaceInit: param %r (a_log or dt_bias)"
                             % (param,))
        self.param, self.low, self.high = param, low, high

    def _init_weight(self, _, arr):
        rng = _random.host_rng()
        if self.param == "a_log":
            arr[:] = np.log(rng.uniform(self.low, self.high, arr.shape))
        else:
            dt = np.exp(rng.uniform(np.log(self.low), np.log(self.high),
                                    arr.shape))
            arr[:] = np.log(np.expm1(dt))


@register
class Normal(Initializer):
    """N(0, sigma^2)."""

    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, _, arr):
        arr[:] = _random.host_rng().normal(0, self.sigma, arr.shape)


@register
class Orthogonal(Initializer):
    """Scaled orthogonal matrix via SVD of a random (nout, nin) draw."""

    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale, self.rand_type = scale, rand_type

    def _init_weight(self, _, arr):
        rows = arr.shape[0]
        cols = int(np.prod(arr.shape[1:]))
        rng = _random.host_rng()
        draw = (rng.uniform(-1.0, 1.0, (rows, cols))
                if self.rand_type == "uniform"
                else rng.normal(0.0, 1.0, (rows, cols)))
        u, _s, v = np.linalg.svd(draw, full_matrices=False)
        basis = u if u.shape == draw.shape else v
        arr[:] = (self.scale * basis).reshape(arr.shape)


def _conv_fans(shape, stacked=False):
    """(fan_in, fan_out) with trailing spatial dims folded in; of one
    [in, out] matrix of a *stacked* [count, in, out] tensor (a variable
    that carries the attribute ``__stacked__``: each expert of a
    sparse-expert layer is a matrix of its own, not a convolution's
    spatial tap)."""
    if stacked:
        if len(shape) != 3:
            raise ValueError("a __stacked__ tensor is [count, in, out], "
                             "got %s" % (tuple(shape),))
        return shape[1], shape[2]
    spatial = np.prod(shape[2:]) if len(shape) > 2 else 1.0
    return shape[1] * spatial, shape[0] * spatial


@register
class Xavier(Initializer):
    """Glorot init: scale^2 = magnitude / factor(fan_in, fan_out)."""

    _FACTORS = {"avg": lambda fi, fo: (fi + fo) / 2.0,
                "in": lambda fi, fo: fi,
                "out": lambda fi, fo: fo}

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type, self.factor_type = rnd_type, factor_type
        self.magnitude = float(magnitude)

    def _init_weight(self, name, arr):
        if len(arr.shape) < 2:
            raise ValueError("Xavier initializer cannot be applied to vector "
                             "%s. It requires at least 2D." % name)
        try:
            factor_fn = self._FACTORS[self.factor_type]
        except KeyError:
            raise ValueError("Incorrect factor type")
        stacked = parse_attr_string(
            getattr(name, "attrs", {}).get("__stacked__", False))
        sigma = np.sqrt(self.magnitude /
                        factor_fn(*_conv_fans(arr.shape, stacked)))
        if self.rnd_type == "uniform":
            arr[:] = _random.host_rng().uniform(-sigma, sigma, arr.shape)
        elif self.rnd_type == "gaussian":
            arr[:] = _random.host_rng().normal(0, sigma, arr.shape)
        else:
            raise ValueError("Unknown random type")


@register
class MSRAPrelu(Xavier):
    """He init adjusted for PReLU slope."""

    def __init__(self, factor_type="avg", slope=0.25):
        super().__init__("gaussian", factor_type, 2.0 / (1 + slope ** 2))
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    """Bilinear-upsampling kernel for Deconvolution (vectorised)."""

    def _init_weight(self, _, arr):
        shape = arr.shape
        f = np.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        xs = np.arange(shape[3], dtype="float32")
        ys = np.arange(shape[2], dtype="float32")
        kernel = np.outer(1 - np.abs(ys / f - c), 1 - np.abs(xs / f - c))
        arr[:] = np.broadcast_to(kernel, shape).astype("float32")


@register
class LSTMBias(Initializer):
    """Zero bias except the forget gate (slot 2 of i,f,g,o)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, desc, arr):
        per_gate = arr.shape[0] // 4
        host = np.zeros(arr.shape, dtype="float32")
        host[per_gate:2 * per_gate] = self.forget_bias
        arr[:] = host
    _init_default = _init_weight
    _init_bias = _init_weight


@register
class FusedRNN(Initializer):
    """Delegates to a wrapped initializer (fused-RNN param blob layout is
    flat on TPU, so no re-packing is needed)."""

    def __init__(self, init=None, state_size=None, num_layers=None, mode=None,
                 bidirectional=False, forget_bias=1.0):
        super().__init__()
        if isinstance(init, Initializer):
            self._init = init
        elif isinstance(init, str) and init:
            self._init = create(init)          # name or JSON form
        else:
            self._init = Uniform(0.1)

    def _init_weight(self, desc, arr):
        self._init._init_weight(desc, arr)
    _init_default = _init_weight


@register
class Load:
    """Copy parameters from a saved dict, else fall back to default_init."""

    def __init__(self, param, default_init=None, verbose=False):
        if isinstance(param, str):
            param = nd.load(param)
        self.param = {key.split(":", 1)[-1]: val
                      for key, val in param.items()}
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr):
        loaded = self.param.get(name)
        if loaded is not None:
            if tuple(loaded.shape) != tuple(arr.shape):
                raise MXNetError(
                    "Parameter %s cannot be initialized from loading. Shape "
                    "mismatch, target %s vs loaded %s"
                    % (name, arr.shape, loaded.shape))
            loaded.copyto(arr)
            return
        if self.default_init is None:
            raise MXNetError(
                "Cannot Initialize parameter %s. Not found in loaded "
                "param and no default initializer" % name)
        self.default_init(name, arr)


@register
class Mixed:
    """First-matching-regex dispatch over a list of initializers."""

    def __init__(self, patterns, initializers):
        if len(patterns) != len(initializers):
            raise ValueError("patterns and initializers must have same length")
        self.map = [(re.compile(p), i)
                    for p, i in zip(patterns, initializers)]

    def __call__(self, name, arr):
        for matcher, initializer in self.map:
            if matcher.match(name):
                initializer(name, arr)
                return
        raise ValueError(
            'Parameter name %s did not match any pattern. Consider adding a '
            '".*" pattern at the end with default Initializer.' % name)


class _InitModule:
    """``mx.init`` namespace shim."""
    Initializer, InitDesc = Initializer, InitDesc
    Zero, One, Constant = Zero, One, Constant
    Uniform, Normal, Orthogonal = Uniform, Normal, Orthogonal
    Xavier, MSRAPrelu, Bilinear = Xavier, MSRAPrelu, Bilinear
    LSTMBias, FusedRNN = LSTMBias, FusedRNN
    Load, Mixed = Load, Mixed


init = _InitModule()
