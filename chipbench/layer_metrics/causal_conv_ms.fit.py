"""First-device time a step inside the sequence convolutions: the Pallas
``causal_conv_fwd`` (twice under recomputation) and ``causal_conv_bwd``,
and the XLA ops around them under the op's scope — ``causal_conv`` for a
biased convolution, ``short_conv`` for a gated one
(``trace_patterns.causal_conv``)."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.ms_per_step(ctx, "causal_conv")
