"""Roofline share of the sliding-window attention ops: what a step
requires of their score and value products over the keys the window
shows (``flops_trinity.py:window_attention_train_work``: 3 x the banded
forward; q, k, v and the output once in the forward, twice in the
backward) over the device time inside them.  Recomputation and the
masked parts of the band's edge tiles are in the time and not in the
work, so the share errs low."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.roofline_pct(ctx, "window_attention",
                                       "window_attention_work")
