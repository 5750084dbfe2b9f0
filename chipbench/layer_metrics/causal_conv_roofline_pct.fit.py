"""Roofline share of the sequence convolutions: what a step requires of
them (``flops_conv.py``: the input read and the output written once in
the forward; the input and the output's cotangent read and the input's
gradient written once in the backward; the taps and the bias once a
pass) over the device time inside them.  The bytes are the floor.  The
replayed forward, the tiles' 16-row tail read twice and the sums of the
taps' gradient over the batch are in the time and not in the work, so
the share errs low."""
from chipbench import kernel_metrics


def read(ctx):
    return kernel_metrics.roofline_pct(ctx, "causal_conv",
                                       "causal_conv_work")
