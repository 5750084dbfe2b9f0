"""Roofline share of the causal attention ops: what a step requires of
their score and value products (``flops_lfm2.py:attention_train_work``:
3 x the causal forward; q, k, v and the output once in the forward, twice
in the backward) over the device time inside them.  Recomputation is in
the time and not in the work, so the share errs low."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.roofline_pct(ctx, "attention", "attention_work")
