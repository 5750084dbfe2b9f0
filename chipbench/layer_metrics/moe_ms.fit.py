"""First-device time a step inside the sparse-expert layers: routing
(scores, top-k, ordering), the grouped products and the combine, forward,
recomputed forward and backward (``trace_patterns.moe``)."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.ms_per_step(ctx, "moe")
