#!/usr/bin/env python
"""Multi-process pjit worker: one SPMD train step over the global mesh.

Run under the launcher (which sets the MXNET_* rendezvous contract):

    python tools/launch.py -n 2 -s 0 python tools/dist_pjit_worker.py

Each process pins LOCAL_DEVICES virtual CPU devices, joins
jax.distributed, and executes the same pjit transformer train step over
the global (num_processes x LOCAL_DEVICES)-device mesh — the north-star
multi-host path (SURVEY §2.5 row 2: jax.distributed over DCN replacing
the ps-lite worker/server fleet).

Prints ``MULTIHOST rank=R world=W ndev=N loss=L`` on success; every rank
must report the identical loss (the program is SPMD).
"""
import os
import sys

# pjit mode needs only the workers; the launcher's scheduler/server roles
# (PS contract) have nothing to do here
if os.environ.get("DMLC_ROLE", "worker") != "worker":
    sys.exit(0)

LOCAL_DEVICES = int(os.environ.get("MX_LOCAL_DEVICES", "4"))

import re
flags = os.environ.get("XLA_FLAGS", "")
flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
os.environ["XLA_FLAGS"] = (
    flags + " --xla_force_host_platform_device_count=%d" % LOCAL_DEVICES
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np  # noqa: E402


def _run_step(devices):
    """Build the tiny LM and run one pjit train step over ``devices``;
    returns (loss, mesh)."""
    from mxnet_tpu.parallel.mesh import make_mesh, factor_devices
    from mxnet_tpu.models.transformer import (
        TransformerLMConfig, init_transformer_params, make_train_step,
        place_batch)

    dims = factor_devices(len(devices), 3)
    mesh = make_mesh({"data": dims[0], "seq": dims[1], "model": dims[2]},
                     devices)
    dp, sp, tp = dims

    cfg = TransformerLMConfig(vocab=64, d_model=8 * max(tp, 1),
                              n_heads=max(tp, 2), d_ff=16 * max(tp, 1),
                              n_layers=2, max_len=8 * max(sp, 1))
    params = init_transformer_params(jax.random.PRNGKey(0), cfg, mesh)

    rng = np.random.RandomState(0)          # same batch on every process
    b, s = 2 * dp, 8 * sp
    tokens = rng.randint(0, cfg.vocab, (b, s)).astype(np.int32)
    labels = rng.randint(0, cfg.vocab, (b, s)).astype(np.int32)
    tokens, labels = place_batch(tokens, labels, mesh)

    step = make_train_step(cfg, mesh, lr=0.1)
    _, loss = step(params, tokens, labels)
    jax.block_until_ready(loss)
    return float(loss), mesh


def main():
    from mxnet_tpu.parallel import multihost

    rank, world = multihost.init_from_env()
    n = len(jax.devices())
    loss, mesh = _run_step(jax.devices())
    assert np.isfinite(loss), loss

    losses = multihost.host_gather_floats("dist_pjit_loss", loss)
    assert len(losses) == world, losses
    assert max(losses) - min(losses) < 1e-6, \
        "ranks disagree on the loss: %r" % (losses,)
    multihost.barrier("dist_pjit_done")
    print("MULTIHOST rank=%d world=%d ndev=%d mesh=%s loss=%.6f"
          % (rank, world, n, dict(mesh.shape), loss), flush=True)


if __name__ == "__main__":
    main()
