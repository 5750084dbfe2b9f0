"""First-device time a step inside the sliding-window attention ops: the
banded Pallas forward (twice under recomputation) and the banded backward
kernels (``trace_patterns.window_attention``)."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.ms_per_step(ctx, "window_attention")
