"""mxnet_tpu: a TPU-native framework with the capabilities of MXNet.

A ground-up JAX/XLA/Pallas re-design of the capability surface of Apache
MXNet v0.12 (reference: jermainewang/mxnet; see SURVEY.md at repo root for
the inventory this build targets).  Eager NDArray + autograd tape on one
side, Symbol/Executor compiling whole graphs to single XLA programs on the
other — the same dual paradigm ("mix symbolic and imperative") the reference
is built around, mapped onto jax eager vs jax.jit.
"""
from __future__ import annotations

__version__ = "0.1.0"

import os as _os

import jax as _jax
# MXNet supports float64/int64 tensors; jax defaults to 32-bit only.
_jax.config.update("jax_enable_x64", True)


def _place_compile_cache():
    """Give jax's persistent compilation cache a home.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this
    function sets nothing — the operator placed the cache.  Unset: the
    cache is ``<checkout>/.jax_cache``, a fixed path (the path is part
    of the cache key, so a directory named after a pid, a time or a
    temp dir would never hit).  Returns the directory in use.
    """
    if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        _jax.config.update(
            "jax_compilation_cache_dir",
            _os.path.join(_os.path.dirname(_os.path.dirname(
                _os.path.abspath(__file__))), ".jax_cache"))
    return _jax.config.jax_compilation_cache_dir


_place_compile_cache()

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus
from . import base
from . import engine
from . import random
from .random import seed
from . import ndarray
from . import ndarray as nd
from . import autograd
from . import attribute
from .attribute import AttrScope
from . import name
from .name import NameManager
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from .executor import Executor
from . import initializer
from .initializer import init
from . import optimizer
from . import optimizer as opt
from . import metric
from . import operator
from . import pallas
from . import stream
from . import rnn
from . import contrib
from . import torch
from . import predict
from .predict import Predictor
from . import lr_scheduler
from . import callback
from . import io
from . import kvstore as kv
from . import kvstore
from . import model
from . import module
from . import module as mod
from .model import FeedForward
from . import recordio
from . import image
from . import gluon
from . import parallel
from . import checkpoint
# models, test_utils, and serving are opt-in imports (mxnet_tpu.models /
# mxnet_tpu.test_utils / mxnet_tpu.serving), keeping `import mxnet_tpu`
# lean like the reference; the serving tier (AOT predict programs +
# continuous batching, docs/SERVING.md) spins up threads and compiles
# programs, so it only loads when a process opts into being a server.
from . import telemetry
from . import profiler
from . import monitor
from .monitor import Monitor
from . import visualization
from . import visualization as viz
from . import log
