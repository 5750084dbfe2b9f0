"""Multi-host runtime: jax.distributed over the launcher's env contract.

Reference analogue: ps-lite's scheduler/server/worker rendezvous driven by
the dmlc tracker env vars (``tools/launch.py:22-30``,
``src/kvstore/kvstore_dist.h``). TPU-native replacement (SURVEY §5.8): all
processes call ``jax.distributed.initialize`` against one coordinator,
after which every host sees the global device set and ``pjit`` programs
run SPMD with XLA collectives over ICI/DCN — there are no parameter
servers to place.

Env contract (either namespace works; the launcher sets both):

    MXNET_COORDINATOR   host:port of process 0   (DMLC_PS_ROOT_URI/_PORT)
    MXNET_NUM_PROCESSES world size               (DMLC_NUM_WORKER)
    MXNET_PROCESS_ID    this process's rank      (DMLC_WORKER_RANK)
"""
from __future__ import annotations

import os

import jax

__all__ = ["init_from_env", "is_initialized", "rank", "num_processes",
           "local_devices", "global_devices", "barrier"]

_initialized = False


def _env(*names, default=None):
    for n in names:
        # one-shot rendezvous read at init, not a hot path
        # graftlint: disable=JG006
        v = os.environ.get(n)
        if v not in (None, ""):
            return v
    return default


def init_from_env(force=False):
    """Initialize jax.distributed when the launcher env vars are present.

    Returns (rank, world_size); (0, 1) when not launched distributed.
    Idempotent — safe to call from library code and user scripts alike.
    """
    global _initialized
    world = int(_env("MXNET_NUM_PROCESSES", "DMLC_NUM_WORKER", default="1"))
    if world <= 1 and not force:
        return 0, 1
    if _initialized:
        return rank(), num_processes()

    # OMPI_COMM_WORLD_RANK / PMI_RANK: the mpi launcher exports one env
    # for the whole worker group, so the per-process rank comes from the
    # MPI runtime itself (ref dmlc_tracker/mpi.py contract)
    proc_id = int(_env("MXNET_PROCESS_ID", "DMLC_WORKER_RANK",
                       "OMPI_COMM_WORLD_RANK", "PMI_RANK", default="0"))
    coord = _env("MXNET_COORDINATOR")
    if coord is None:
        host = _env("DMLC_PS_ROOT_URI", default="127.0.0.1")
        port = _env("MXNET_COORDINATOR_PORT", "DMLC_PS_ROOT_PORT",
                    default="49151")
        coord = "%s:%s" % (host, port)

    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=world, process_id=proc_id)
    _initialized = True
    return proc_id, world


def is_initialized():
    return _initialized


def rank():
    """This process's index (ref kvstore.h:309 get_rank)."""
    return jax.process_index()


def num_processes():
    """World size (ref kvstore.h:316 get_group_size)."""
    return jax.process_count()


def local_devices():
    return jax.local_devices()


def global_devices():
    return jax.devices()


def _coord_client():
    """The jax.distributed coordination-service client, or None.  Its
    barrier/KV ops are plain gRPC to the coordinator — no XLA program,
    so they cost no compile and work before any mesh exists."""
    try:
        from jax._src import distributed
        return distributed.global_state.client
    except Exception:
        return None


def barrier(name="mx_barrier", timeout_ms=600_000):
    """Block until every process arrives (ref kvstore.h:339 Barrier).

    Prefers the coordination-service barrier (host-level, backend-
    independent); falls back to a tiny all-reduce whose completion is
    the synchronisation.
    """
    if jax.process_count() == 1:
        return
    client = _coord_client()
    if client is not None:
        client.wait_at_barrier(name, timeout_in_ms=timeout_ms)
        return
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(name)


def host_gather_floats(name, value, timeout_ms=600_000):
    """Every process contributes one float; returns the rank-ordered
    list on all of them.  Rides the coordination-service KV store
    (host-level), so it agrees values across processes even when the
    backend can't compile a cross-process program."""
    world = jax.process_count()
    if world == 1:
        return [float(value)]
    client = _coord_client()
    if client is None:
        raise RuntimeError("host_gather_floats needs jax.distributed")
    client.key_value_set("%s/%d" % (name, jax.process_index()),
                         repr(float(value)))
    return [float(client.blocking_key_value_get(
        "%s/%d" % (name, r), timeout_ms)) for r in range(world)]
