"""Operations each configuration requires, computed from its shapes: the
only place a FLOP count is defined for the benchmark.  Analytic — what the
model needs, not what XLA emitted.  Convolutions and the classifier count;
BatchNorm, ReLU, pooling and the bias adds do not (the papers' counts leave
them out as well).

A configuration file names its function as ``"flops": "module:function"``;
the function takes the configuration and returns the multiply-adds of ONE
item's forward pass.  A training step requires 3x the forward (forward,
gradient by input, gradient by weight), and a multiply-add is 2 operations.
"""
import importlib


def conv_macs(out_hw, c_in, c_out, k, groups=1):
    return out_hw * out_hw * c_out * (c_in // groups) * k * k


def out_size(size, k, stride, pad):
    return (size + 2 * pad - k) // stride + 1


def resnet_v1_bottleneck(cfg):
    """He et al. 2015 Table 1, stride in a stage's first 1x1."""
    ch = cfg["channels"]
    hw = out_size(cfg["image"], 7, 2, 3)
    macs = conv_macs(hw, 3, ch[0], 7)
    hw = out_size(hw, 3, 2, 1)                      # max pool
    width_in = ch[0]
    for stage, (blocks, width) in enumerate(zip(cfg["layers"], ch[1:])):
        for block in range(blocks):
            stride = 2 if (block == 0 and stage > 0) else 1
            out = out_size(hw, 1, stride, 0)
            macs += conv_macs(out, width_in, width // 4, 1)
            macs += conv_macs(out, width // 4, width // 4, 3)
            macs += conv_macs(out, width // 4, width, 1)
            if block == 0 and width != width_in:
                macs += conv_macs(out, width_in, width, 1)
            hw, width_in = out, width
    return macs + width_in * cfg["classes"]


def mobilenet_v1(cfg):
    """Howard et al. 2017 Table 1."""
    from chipbench.reference.mobilenet_v1 import PLAN
    m = cfg["multiplier"]
    width = int(32 * m)
    hw = out_size(cfg["image"], 3, 2, 1)
    macs = conv_macs(hw, 3, width, 3)
    for out_ch, stride in PLAN:
        hw = out_size(hw, 3, stride, 1)
        macs += conv_macs(hw, width, width, 3, groups=width)
        nxt = int(out_ch * m)
        macs += conv_macs(hw, width, nxt, 1)
        width = nxt
    return macs + width * cfg["classes"]


def forward_macs(cfg):
    module, _, fn = cfg["flops"].partition(":")
    return getattr(importlib.import_module(module), fn)(cfg)


def train_flops_per_item(cfg):
    return forward_macs(cfg) * 2 * 3


def infer_flops_per_item(cfg):
    return forward_macs(cfg) * 2
