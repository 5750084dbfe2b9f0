"""First-device time a step inside the causal attention ops: the Pallas
forward (twice under recomputation) and the chunked backward
(``trace_patterns.attention``)."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.ms_per_step(ctx, "attention")
