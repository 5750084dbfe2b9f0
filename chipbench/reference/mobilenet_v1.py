"""MobileNet v1 — Howard et al., arXiv:1704.04861, Table 1: a 3x3/2 full
convolution, then 13 depthwise-separable blocks (3x3 depthwise, 1x1
pointwise, each followed by BatchNorm and ReLU), global average pool, FC.
"""
from . import layers as L

# Table 1: (pointwise output channels, depthwise stride)
PLAN = [(64, 1), (128, 2), (128, 1), (256, 2), (256, 1), (512, 2),
        (512, 1), (512, 1), (512, 1), (512, 1), (512, 1), (1024, 2),
        (1024, 1)]


def forward(cfg, params, aux, x, dtype, train):
    """float32 images (N,3,H,W) -> (float32 logits, BatchNorm statistics)."""
    pre = L.model_prefix(params)
    net = L.Net(params, aux, dtype, train)
    i = 0

    def unit(x, stride=1, pad=0, groups=1):
        nonlocal i
        x = net.conv(x, "%sconv2d%d" % (pre, i), stride, pad, groups)
        x = L.relu(net.bn(x, "%sbatchnorm%d" % (pre, i)))
        i += 1
        return x

    width = int(32 * cfg["multiplier"])
    x = unit(x.astype(dtype), 2, 1)
    for out, stride in PLAN:
        x = unit(x, stride, 1, groups=width)        # depthwise
        width = int(out * cfg["multiplier"])
        x = unit(x)                                 # pointwise
    x = L.global_avg_pool(x)
    logits = L.dense(x, net.w(pre + "dense0_weight"),
                     net.w(pre + "dense0_bias"))
    return net.finish(logits)
