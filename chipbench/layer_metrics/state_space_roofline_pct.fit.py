"""Roofline share of the state-space scan ops: what a step requires of
the scans (``flops_granite.py:state_space_train_work``: 3 x the chunked
dual form's forward at the published chunk; x, B, C, dt and y once in the
forward, those, dy and the four gradients once in the backward) over the
device time inside them.  At the published widths the byte term is the
floor.  The states pass, the tiles' padding and whatever a kernel reads
twice are in the time and not in the work, so the share errs low."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.roofline_pct(ctx, "state_space",
                                       "state_space_work")
