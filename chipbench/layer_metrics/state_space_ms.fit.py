"""First-device time a step inside the state-space scan ops: the Pallas
forward, the backward's states pass and its reverse pass, and the XLA ops
around them under the ops' scopes (``trace_patterns.state_space``).  A
recomputation segment keeps the scan's output, so no forward is replayed
in it."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.ms_per_step(ctx, "state_space")
