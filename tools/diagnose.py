#!/usr/bin/env python
"""Environment/version diagnostics.

Reference counterpart: ``tools/diagnose.py`` — dump platform, python,
framework, and accelerator information for bug reports.

    python tools/diagnose.py
"""
import os
import platform
import sys


def main():
    print("----------Platform Info----------")
    print("Platform     :", platform.platform())
    print("system       :", platform.system())
    print("release      :", platform.release())
    print("machine      :", platform.machine())

    print("----------Python Info----------")
    print("version      :", platform.python_version())
    print("executable   :", sys.executable)

    print("----------Framework Info----------")
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), ".."))
    try:
        import mxnet_tpu as mx
        print("mxnet_tpu    :", mx.__version__)
        print("location     :", os.path.dirname(mx.__file__))
        from mxnet_tpu.ops.registry import OP_REGISTRY
        print("operators    :", len(OP_REGISTRY))
    except Exception as exc:
        print("mxnet_tpu    : import failed:", exc)

    print("----------JAX / Device Info----------")
    try:
        import jax
        print("jax          :", jax.__version__)
        # initializes the backend: on a TPU host this process holds the
        # chip until it exits (one process per chip)
        devs = jax.devices()
        print("devices      :", devs)
        print("device_kind  :", devs[0].device_kind)
    except Exception as exc:
        print("jax          : import failed:", exc)

    print("----------Environment----------")
    env = dict(os.environ)     # one snapshot, not a read per iteration
    for key in sorted(env):
        if key.startswith(("MXNET_", "DMLC_", "JAX_", "XLA_")):
            print("%-28s: %s" % (key, env[key]))


if __name__ == "__main__":
    main()
