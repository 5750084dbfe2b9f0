"""User-facing Pallas kernel registration — the TPU answer to RTC.

Reference parity: ``python/mxnet/rtc.py`` + ``src/common/rtc.cc:32-80``
let a user hand the runtime raw CUDA source (``CudaModule(source)
.get_kernel(...).launch(...)``) and call it on NDArrays. On TPU the
user-authored kernel is a **Pallas** function instead of CUDA source, and
"launching" means installing it in the operator registry so it is usable
from every frontend — ``mx.nd.<name>``, ``mx.sym.<name>``, hybridized
Gluon blocks, Module training — exactly like a built-in op:

    import mxnet_tpu as mx
    from jax.experimental import pallas as pl

    def _scale_kernel(x_ref, o_ref, *, alpha):
        o_ref[...] = x_ref[...] * alpha

    @mx.pallas.register("my_scale", grad=lambda og, ins, outs, attrs:
                        (og[0] * float(attrs.get("alpha", 1.0)),))
    def my_scale(x, alpha=2.0, interpret=False):
        import functools
        return pl.pallas_call(
            functools.partial(_scale_kernel, alpha=float(alpha)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=interpret)(x)

    y = mx.nd.my_scale(mx.nd.ones((4, 4)), alpha=3.0)   # eager
    s = mx.sym.my_scale(mx.sym.Variable("d"), alpha=3.0)  # symbolic

Kernels compile with Mosaic, which needs a TPU.  A kernel that accepts
an ``interpret`` keyword gets ``False`` unless the registration
(``register(..., interpret=True)``) or the call site
(``mx.nd.my_scale(x, interpret=True)``) says otherwise: the Pallas
interpreter is the explicit CPU-test mode, never a silent fallback — a
compiled kernel on a host without a TPU raises.

Mosaic has no 64-bit types and this package enables ``jax_enable_x64``:
give constants inside a kernel body an explicit 32-bit dtype
(``np.float32(-1e30)``, not ``-1e30``) wherever they pass through a
jitted ``jnp`` helper such as ``jnp.where``, or lowering fails with
``Unsupported cast: float64 -> float32``.

Gradients: pure-JAX ops differentiate through ``jax.vjp`` automatically;
``pl.pallas_call`` does not, so kernels used in training either pass
``grad=`` (a semantic backward like the reference's custom FGradient) or
register a companion backward kernel.
"""
from __future__ import annotations

import inspect

from .base import MXNetError
from .ops.registry import OP_REGISTRY, Op

__all__ = ["register", "unregister", "registered_kernels"]

_USER_KERNELS = []
_SHADOWED = {}  # name -> Op it force-replaced, restored on unregister()


def _expose(name, op):
    """Install the nd/sym wrappers for a freshly registered op (the
    import-time generation in ndarray/__init__ and symbol/__init__ has
    already run by the time a user registers a kernel)."""
    import sys
    from . import ndarray as nd_mod
    from . import symbol as sym_mod
    from .ndarray import _make_op_func
    from .symbol import _make_sym_func

    nd_fn = _make_op_func(name, op)
    sym_fn = _make_sym_func(name, op)
    setattr(sys.modules[nd_mod.__name__ + "._internal"], name, nd_fn)
    setattr(sys.modules[sym_mod.__name__ + "._internal"], name, sym_fn)
    if not name.startswith("_"):
        setattr(nd_mod, name, nd_fn)
        setattr(sym_mod, name, sym_fn)
    return nd_fn


def register(name, fn=None, *, grad=None, num_outputs=1, takes_mode=False,
             needs_rng=False, interpret=False, force=False):
    """Register *fn* as operator *name*, usable from nd/sym/gluon.

    Parameters
    ----------
    fn : pure function ``(*jax_arrays, **attrs) -> array | tuple`` —
        typically wrapping ``pl.pallas_call``. If it accepts an
        ``interpret`` keyword, the registry fills it with *interpret*
        unless the call site pins it.
    grad : optional semantic backward
        ``bwd(out_grads, inputs, outputs, attrs) -> input_grads`` (tuple,
        one per input). Without it, gradients flow through ``jax.vjp`` —
        fine for pure-JAX bodies, unavailable for raw pallas_call.
    interpret : run the kernel in the Pallas interpreter (True; CPU
        tests) instead of compiling it with Mosaic (False, the default).
    force : allow replacing an existing registration.

    Returns the eager ``mx.nd.<name>`` callable (decorator-friendly).
    """
    if fn is None:  # decorator form
        def deco(f):
            return register(name, f, grad=grad, num_outputs=num_outputs,
                            takes_mode=takes_mode, needs_rng=needs_rng,
                            interpret=interpret, force=force)
        return deco
    if name in OP_REGISTRY:
        if not force:
            raise MXNetError(
                "operator %r already registered (pass force=True to replace)"
                % name)
        if name not in _SHADOWED and name not in _USER_KERNELS:
            # force=True over a built-in: stash it so unregister() restores
            # the core operator instead of deleting it (r4 advice).
            _SHADOWED[name] = OP_REGISTRY[name]

    params = inspect.signature(fn).parameters
    accepts_interpret = "interpret" in params

    if accepts_interpret:
        def body(*arrays, **attrs):
            if attrs.get("interpret") is None:
                attrs["interpret"] = interpret
            return fn(*arrays, **attrs)
        body.__name__ = getattr(fn, "__name__", name)
    else:
        body = fn

    op = Op(name, body, num_outputs=num_outputs, takes_mode=takes_mode,
            needs_rng=needs_rng, custom_vjp=grad,
            attr_defaults={"interpret": None} if accepts_interpret else None)
    OP_REGISTRY[name] = op
    if name not in _USER_KERNELS:
        _USER_KERNELS.append(name)
    return _expose(name, op)


def unregister(name):
    """Remove a user-registered kernel and its nd/sym wrappers
    (built-ins are protected)."""
    import sys
    from . import ndarray as nd_mod
    from . import symbol as sym_mod
    if name not in _USER_KERNELS:
        raise MXNetError("%r is not a user-registered kernel" % name)
    _USER_KERNELS.remove(name)
    OP_REGISTRY.pop(name, None)
    for mod in (nd_mod, sym_mod,
                sys.modules.get(nd_mod.__name__ + "._internal"),
                sys.modules.get(sym_mod.__name__ + "._internal")):
        if mod is not None and hasattr(mod, name):
            delattr(mod, name)
    shadowed = _SHADOWED.pop(name, None)
    if shadowed is not None:
        # the kernel force-replaced a built-in: put the original back,
        # wrappers included, so the framework keeps its core operator
        OP_REGISTRY[name] = shadowed
        _expose(name, shadowed)


def registered_kernels():
    """Names of live user-registered kernels."""
    return list(_USER_KERNELS)
