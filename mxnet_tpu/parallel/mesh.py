"""Device-mesh construction and management — THE sharding substrate.

The reference expresses multi-device placement as a context list handed to
``Module``/``DataParallelExecutorGroup`` (reference ``module/module.py:39``,
``executor_group.py:233``).  TPU-native, placement is a ``jax.sharding.Mesh``
with named axes; data parallelism shards the batch over ``"data"``, tensor
parallelism shards weights over ``"model"``, sequence parallelism shards the
sequence over ``"seq"``.  Collectives ride ICI within a slice and DCN across
slices — axis order puts the fastest-varying (innermost) axis on the
best-connected devices.

This module is the single owner of three things every SPMD consumer
(models, pipeline, ring attention, ZeRO placement, fused executor group)
used to carry privately:

1. **Mesh construction** — local single-host meshes (:func:`make_mesh`,
   :func:`auto_mesh`) and the multi-host topology where the
   jax.distributed process fleet is a first-class leading axis
   (:func:`multihost_mesh`); ``MXNET_MESH_SHAPE`` /
   ``MXNET_MESH_SPAN_HOSTS`` select a fleet-wide default without code
   changes (:func:`mesh_from_env`).
2. **Sharding helpers** — :func:`filter_spec` (one model definition runs
   on dp-only, dp+tp, or dp+tp+sp meshes), :func:`named_sharding`,
   :func:`replicated`, and :func:`shard_put` (multi-process-safe
   placement: each process materializes only its addressable shards).
3. **Program entry points** — :func:`shard_map`, the one wrapper over
   ``jax.shard_map`` (mesh defaulting + the ``check`` switch for
   ``check_vma``), plus the :func:`pvary` / :func:`vma_axes` helpers its
   scan-carrying callers need; and
   :func:`jit_sharded`, ``jax.jit`` + ``watch_jit`` in one call so every
   SPMD program lands in the telemetry retrace watchdog, cost accounting
   and ``MXNET_DEVICE_TIME`` attribution from day one.

No other module in the tree may call ``shard_map`` directly — graftcheck's
coverage gate and tests/test_mesh.py enforce the single-substrate rule.
"""
from __future__ import annotations

import contextlib
import os
import threading

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["make_mesh", "auto_mesh", "factor_devices", "current_mesh",
           "using_mesh", "shard_map", "pvary", "vma_axes", "filter_spec",
           "named_sharding", "replicated", "shard_put", "jit_sharded",
           "multihost_mesh", "mesh_from_env", "default_mesh", "topology",
           "refresh_from_env"]

_tls = threading.local()


def factor_devices(n, num_axes):
    """Factor ``n`` devices into ``num_axes`` near-balanced mesh dims.

    Largest factors go first (outermost); e.g. 8 devices, 3 axes →
    (2, 2, 2); 8 devices, 2 axes → (4, 2); 6, 2 → (3, 2).
    """
    dims = []
    remaining = n
    for i in range(num_axes - 1, 0, -1):
        # greedily peel the smallest factor > 1 for the innermost axes
        target = max(2, int(round(remaining ** (1.0 / (i + 1)))))
        f = 1
        for cand in range(target, 1, -1):
            if remaining % cand == 0:
                f = cand
                break
        if f == 1:
            for cand in range(target + 1, remaining + 1):
                if remaining % cand == 0:
                    f = cand
                    break
        dims.append(f)
        remaining //= f
    dims.append(remaining)
    return tuple(sorted(dims, reverse=True))


def make_mesh(axis_shapes, devices=None):
    """Create a ``Mesh`` from ``{axis_name: size}`` (insertion-ordered).

    ``-1`` for at most one axis means "all remaining devices".
    """
    if devices is None:
        devices = jax.devices()
    names = list(axis_shapes.keys())
    sizes = list(axis_shapes.values())
    n = len(devices)
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(
                "cannot infer -1 axis: %d devices not divisible by %d"
                % (n, known))
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError("mesh %s needs %d devices, only %d available"
                         % (dict(zip(names, sizes)), total, n))
    dev_array = np.asarray(devices[:total]).reshape(sizes)
    return Mesh(dev_array, tuple(names))


def auto_mesh(axis_names=("data",), n_devices=None, devices=None):
    """Mesh over all (or ``n_devices``) devices, balanced across axes."""
    if devices is None:
        devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    dims = factor_devices(len(devices), len(axis_names))
    return make_mesh(dict(zip(axis_names, dims)), devices)


def current_mesh():
    """The innermost active mesh (from ``using_mesh``), or None."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


@contextlib.contextmanager
def using_mesh(mesh):
    """Activate ``mesh`` for the enclosed scope (and as jax's global mesh)."""
    if not hasattr(_tls, "stack"):
        _tls.stack = []
    _tls.stack.append(mesh)
    try:
        with mesh:
            yield mesh
    finally:
        _tls.stack.pop()


# --------------------------------------------------------------------------
# Multi-host topology: the jax.distributed fleet as a first-class axis
# --------------------------------------------------------------------------

def multihost_mesh(axis_shapes=None, host_axis="host", devices=None,
                   n_hosts=None):
    """A mesh spanning every jax.distributed process, with the process
    fleet as the leading ``host_axis`` and ``axis_shapes`` (default
    ``{"data": -1}``) laid over each host's devices.

    This is the dist_ps worker fleet become a mesh dimension: collectives
    over ``host_axis`` ride DCN between processes, the inner axes ride
    ICI within each host.  ``devices``/``n_hosts`` are injectable so a
    faked multi-host topology (one process, N virtual hosts) is testable
    on CPU — production callers pass neither and get the live
    ``jax.devices()`` / ``jax.process_count()`` fleet.
    """
    if devices is None:
        devices = jax.devices()
    if n_hosts is None:
        n_hosts = jax.process_count()
    n_hosts = max(1, int(n_hosts))
    if len(devices) % n_hosts:
        raise ValueError(
            "multihost mesh: %d devices not divisible by %d hosts"
            % (len(devices), n_hosts))
    shapes = {host_axis: n_hosts}
    for name, size in (axis_shapes or {"data": -1}).items():
        if name == host_axis:
            raise ValueError("axis %r collides with host axis" % name)
        shapes[name] = size
    return make_mesh(shapes, devices)


def topology():
    """One JSON-shaped dict describing the device fleet this process can
    build meshes over (the MULTICHIP dryrun and docs/SPMD.md contract)."""
    devices = jax.devices()
    return {
        "n_devices": len(devices),
        "n_local_devices": len(jax.local_devices()),
        "n_hosts": jax.process_count(),
        "process_index": jax.process_index(),
        "platform": devices[0].platform if devices else None,
    }


# --------------------------------------------------------------------------
# Env-selected default mesh (MXNET_MESH_* knobs)
# --------------------------------------------------------------------------

def _parse_mesh_shape(text):
    """``"data=-1,model=2"`` → {"data": -1, "model": 2} (ordered)."""
    shapes = {}
    for part in text.split(","):
        part = part.strip()
        if not part:
            continue
        if "=" not in part:
            raise ValueError(
                "MXNET_MESH_SHAPE entry %r is not name=size" % part)
        name, _, size = part.partition("=")
        shapes[name.strip()] = int(size)
    if not shapes:
        raise ValueError("MXNET_MESH_SHAPE set but empty")
    return shapes


def _env_mesh_config():
    shape = os.environ.get("MXNET_MESH_SHAPE", "").strip()
    span = os.environ.get("MXNET_MESH_SPAN_HOSTS", "0").strip()
    return (_parse_mesh_shape(shape) if shape else None,
            span not in ("", "0", "false", "False"))


# cached at import (the JG006 pattern); refresh_from_env re-reads
_ENV_SHAPE, _ENV_SPAN_HOSTS = _env_mesh_config()


def refresh_from_env():
    """Re-read MXNET_MESH_SHAPE / MXNET_MESH_SPAN_HOSTS (tests / late
    configuration)."""
    global _ENV_SHAPE, _ENV_SPAN_HOSTS
    _ENV_SHAPE, _ENV_SPAN_HOSTS = _env_mesh_config()


def mesh_from_env(devices=None):
    """The fleet-selected mesh, or None when ``MXNET_MESH_SHAPE`` is
    unset.  ``MXNET_MESH_SHAPE="data=-1,model=2"`` names the axes and
    sizes (one ``-1`` = all remaining devices);
    ``MXNET_MESH_SPAN_HOSTS=1`` prepends the jax.distributed process
    fleet as a leading ``host`` axis (:func:`multihost_mesh`)."""
    if _ENV_SHAPE is None:
        return None
    if _ENV_SPAN_HOSTS:
        return multihost_mesh(_ENV_SHAPE, devices=devices)
    return make_mesh(_ENV_SHAPE, devices=devices)


def default_mesh(axis_names=("data",)):
    """The mesh an SPMD consumer should use when none was passed: the
    innermost ``using_mesh``, else the ``MXNET_MESH_*`` env selection,
    else all devices balanced over ``axis_names``."""
    mesh = current_mesh()
    if mesh is not None:
        return mesh
    mesh = mesh_from_env()
    if mesh is not None:
        return mesh
    return auto_mesh(axis_names)


# --------------------------------------------------------------------------
# Sharding helpers
# --------------------------------------------------------------------------

def filter_spec(spec, mesh):
    """Drop axis names the mesh doesn't have (lets one model definition
    run on dp-only, dp+tp, or dp+tp+sp meshes)."""
    if mesh is None:
        return spec
    names = mesh.axis_names
    return P(*[a if a in names else None for a in spec])


def named_sharding(mesh, spec):
    """``NamedSharding(mesh, filter_spec(spec, mesh))`` — the one spelling
    of "this spec, on this mesh, minus axes the mesh lacks"."""
    return NamedSharding(mesh, filter_spec(spec, mesh))


def replicated(mesh):
    """Fully replicated NamedSharding on ``mesh``."""
    return NamedSharding(mesh, P())


def shard_put(value, sharding, spec=None):
    """Place a host value under *sharding*, working in multi-process SPMD
    too: each process materializes only its addressable shards
    (jax.make_array_from_callback), so the same call serves one host or a
    jax.distributed fleet.  ``sharding`` may be a Mesh when ``spec`` is
    given."""
    if isinstance(sharding, Mesh):
        sharding = named_sharding(sharding, P() if spec is None else spec)
    if jax.process_count() == 1:
        return jax.device_put(value, sharding)
    host = np.asarray(value)
    return jax.make_array_from_callback(host.shape, sharding,
                                        lambda idx: host[idx])


# --------------------------------------------------------------------------
# Program entry points: shard_map and watched jit
# --------------------------------------------------------------------------

def shard_map(fn, mesh=None, in_specs=None, out_specs=None, check=None):
    """Map ``fn`` over mesh shards with explicit collectives — the ONE
    ``jax.shard_map`` call site in the tree.  ``check=False`` disables
    the varying-manual-axes checker (``check_vma``); ``mesh`` defaults to
    the innermost :func:`using_mesh` scope.
    """
    if mesh is None:
        mesh = current_mesh()
        if mesh is None:
            raise ValueError(
                "shard_map: no mesh passed and no using_mesh() scope "
                "active")
    kwargs = {}
    if check is not None:
        kwargs["check_vma"] = check
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)


def vma_axes(*arrays, extra=()):
    """The union of mesh axes ``arrays`` are device-varying over, plus
    ``extra`` — the axes a shard_map scan carry must be cast to."""
    axes = set(extra)
    for a in arrays:
        axes |= set(jax.typeof(a).vma)
    return tuple(sorted(axes))


def pvary(x, axes):
    """Cast ``x`` to be device-varying over ``axes`` inside shard_map."""
    if not axes:
        return x
    return jax.lax.pcast(x, tuple(axes), to="varying")


def jit_sharded(fn, name, **jit_kwargs):
    """``watch_jit(jax.jit(fn, **jit_kwargs), name)`` — every SPMD
    program the framework owns goes through here so it lands in the
    retrace watchdog, XLA cost accounting, MXNET_DEVICE_TIME attribution
    and the MXNET_TRACECHECK hook with one line."""
    from .. import telemetry as _tel
    return _tel.watch_jit(jax.jit(fn, **jit_kwargs), name)
