"""ResNet v1 with bottleneck blocks — He et al., arXiv:1512.03385, Table 1
(50-layer column with layers=[3,4,6,3]) and Fig. 5 right.

Departures from the paper, both the zoo's and both noted in the config:
the stride of a stage's first block sits in its first 1x1 convolution (the
paper's original placement; "v1.5" moved it to the 3x3), and the 1x1
convolutions of a block's body carry a bias (the Gluon zoo's quirk — the
projection shortcut, the 3x3 and the stem do not).
"""
from . import layers as L


def forward(cfg, params, aux, x, dtype, train):
    """float32 images (N,3,H,W) -> (float32 logits, BatchNorm statistics)."""
    pre = L.model_prefix(params)
    net = L.Net(params, aux, dtype, train)
    x = x.astype(dtype)
    x = net.conv(x, pre + "conv2d0", stride=2, pad=3)
    x = L.relu(net.bn(x, pre + "batchnorm0"))
    x = L.max_pool(x, 3, 2, 1)
    width_in = cfg["channels"][0]
    for stage, (blocks, width) in enumerate(
            zip(cfg["layers"], cfg["channels"][1:]), start=1):
        sp = "%sstage%d_" % (pre, stage)
        conv_i = bn_i = 0

        def unit(x, stride=1, pad=0):
            nonlocal conv_i, bn_i
            x = net.conv(x, "%sconv2d%d" % (sp, conv_i), stride, pad)
            x = net.bn(x, "%sbatchnorm%d" % (sp, bn_i))
            conv_i += 1
            bn_i += 1
            return x

        for block in range(blocks):
            stride = 2 if (block == 0 and stage > 1) else 1
            y = L.relu(unit(x, stride))             # 1x1, width/4
            y = L.relu(unit(y, 1, 1))               # 3x3, width/4
            y = unit(y)                             # 1x1, width
            if block == 0 and width != width_in:    # projection shortcut
                x = unit(x, stride)
            x = L.relu(y + x)
        width_in = width
    x = L.global_avg_pool(x)
    logits = L.dense(x, net.w(pre + "dense0_weight"),
                     net.w(pre + "dense0_bias"))
    return net.finish(logits)
