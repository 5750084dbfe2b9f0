"""Device contexts for a TPU-native runtime.

Parity surface: reference ``python/mxnet/context.py`` (``Context``, ``cpu()``,
``gpu()``, ``current_context()``).  TPU-first redesign: contexts resolve to JAX
devices; ``tpu(i)`` is first-class; ``gpu(i)`` is accepted for source
compatibility with reference examples and resolves to the i-th accelerator
(TPU chip here).

A context names exactly the device it says, or raises:

- ``cpu(i)`` is always the host CPU backend (reference parity), also on a
  machine with chips attached.
- ``tpu(i)`` / ``gpu(i)`` is the i-th attached accelerator and raises
  ``MXNetError`` when there is none — it never resolves to a CPU device.
- the *default* context (what ``current_context()`` returns outside any
  ``with ctx:`` scope, and therefore what ``Module``, ``Predictor``,
  ``serving.load``, ``nd.array`` ... use when the caller names no
  context) is ``tpu(0)`` when an accelerator is attached and ``cpu(0)``
  otherwise, so unmodified scripts land on the chip and
  ``JAX_PLATFORMS=cpu`` runs are unchanged.
"""
from __future__ import annotations

import threading

import jax

__all__ = ["Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
           "num_gpus", "num_tpus", "device_mesh"]

_DEVTYPE2ID = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "tpu": 6}
_ID2DEVTYPE = {v: k for k, v in _DEVTYPE2ID.items()}


def _accelerator_devices():
    """The attached non-CPU JAX devices ([] on a CPU-only host)."""
    return [d for d in jax.devices() if d.platform != "cpu"]


class Context:
    """A device context. Constructing it never allocates; it is a name.

    Reference semantics kept: ``Context('cpu', 0)``, equality, hashing,
    ``with ctx:`` to set the default, ``device_typeid`` codes for
    serialization.
    """

    _default_ctx = threading.local()
    devtype2str = _ID2DEVTYPE
    devstr2type = _DEVTYPE2ID

    def __init__(self, device_type, device_id=0):
        if isinstance(device_type, Context):
            self.device_type, self.device_id = device_type.device_type, device_type.device_id
        else:
            if device_type not in _DEVTYPE2ID:
                raise ValueError("unknown device type %r" % (device_type,))
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx = None

    @property
    def device_typeid(self):
        return _DEVTYPE2ID[self.device_type]

    # -- JAX resolution ----------------------------------------------------
    @property
    def jax_device(self):
        """Resolve to a concrete jax.Device.

        ``cpu`` → host CPU backend (ids past the last host device name
        the last one: every cpu id is the same host, as in the
        reference); ``tpu``/``gpu`` → i-th accelerator, ``MXNetError``
        if it is not attached.
        """
        if self.device_type in ("cpu", "cpu_pinned"):
            cpus = jax.devices("cpu")
            return cpus[min(self.device_id, len(cpus) - 1)]
        devs = _accelerator_devices()
        if self.device_id >= len(devs):
            raise MXNetErrorForDevice(self, len(devs))
        return devs[self.device_id]

    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __str__(self):
        return "%s(%d)" % (self.device_type, self.device_id)

    __repr__ = __str__

    def __enter__(self):
        self._old_ctx = current_context()
        Context._default_ctx.value = self
        return self

    def __exit__(self, *exc):
        Context._default_ctx.value = self._old_ctx

    def empty_cache(self):
        """Parity no-op: XLA owns HBM pooling (reference: GPUPooledStorageManager)."""


def MXNetErrorForDevice(ctx, n):
    from .base import MXNetError
    return MXNetError(
        "%s is not attached: jax sees %d accelerator device(s) (default "
        "backend %r); use cpu() to name the host"
        % (ctx, n, jax.default_backend()))


def cpu(device_id=0):
    return Context("cpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def gpu(device_id=0):
    """Source-compat alias: reference examples say ``mx.gpu(i)``; on this
    runtime it names the i-th accelerator chip (TPU)."""
    return Context("gpu", device_id)


def tpu(device_id=0):
    return Context("tpu", device_id)


def current_context():
    """The innermost ``with ctx:`` scope, else the default context:
    accelerator 0 when one is attached, the host otherwise."""
    if getattr(Context._default_ctx, "value", None) is None:
        Context._default_ctx.value = Context(
            "tpu" if _accelerator_devices() else "cpu", 0)
    return Context._default_ctx.value


def num_gpus():
    """Number of attached accelerator chips (reference: mx.context.num_gpus).
    A backend that fails to initialize raises; it is not counted as 0."""
    return len(_accelerator_devices())


def num_tpus():
    return num_gpus()


def device_mesh(ctx_list=None, axis_name="dp"):
    """Build a 1-D ``jax.sharding.Mesh`` from a context list.

    This is the TPU-native replacement for MXNet's multi-context
    data-parallel idiom (``ctx=[mx.gpu(0), mx.gpu(1), ...]``): instead of one
    executor per device, we build a mesh and shard the batch axis over it.
    """
    from jax.sharding import Mesh
    import numpy as np
    if ctx_list is None:
        devs = jax.devices()        # the default backend's devices
    else:
        devs = [Context(c).jax_device if not isinstance(c, Context) else c.jax_device
                for c in ctx_list]
    return Mesh(np.array(devs), (axis_name,))
