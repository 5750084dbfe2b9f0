"""First-device time a step inside the routing of the sparse-expert layers
(``trace_patterns.moe_route``): the router's product, the softmax, the
top-k, both sorts and the group sizes, forward, recomputed forward and
backward.  A family whose router reads the attention's input could run it
beside attention and hide it; on one chip nothing hides it, and this is
its price."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.ms_per_step(ctx, "moe_route")
