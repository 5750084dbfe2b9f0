"""Roofline share of the sparse-expert layers: what a step requires of
them (``chipbench/flops_lfm2.py:expert_train_work``: 3 x the forward's
router and routed SwiGLU products; the held weights once a pass, their
gradient once, the routed rows in and out) over the device time inside
them.  Recomputation is in the time and not in the work, so the share
errs low."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.roofline_pct(ctx, "moe", "expert_work")
