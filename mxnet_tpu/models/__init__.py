"""First-class model definitions built on the parallel layer.

The Gluon model zoo (``mxnet_tpu.gluon.model_zoo``) carries the reference's
vision families (SURVEY §2.3); this package holds TPU-native SPMD models —
the transformer LM with data/tensor/sequence parallel shardings, used by
the scale-out benchmarks and the multi-chip dry run — and ``brumby``, a
power-retention LM as a ``Symbol`` for ``Module.fit``.
"""
from .transformer import (TransformerLMConfig, init_transformer_params,
                          transformer_forward, make_train_step)
from .brumby import brumby_symbol

__all__ = ["TransformerLMConfig", "init_transformer_params",
           "transformer_forward", "make_train_step", "brumby_symbol"]
