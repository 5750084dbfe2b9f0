"""Operations and bytes a training step requires of the sequence
convolution: the depthwise causal taps along the sequence, with
``granitemoehybrid``'s bias and SiLU or ``lfm2_moe``'s two gates, from the
configuration's shapes alone.  Multiply-accumulates of the taps ("macs");
a FLOP count is 2 x macs, the gates and the activation are vector work
and not counted.  The op is bound by HBM: forward, one read of its input
and one write of its output; backward, its input and the output's
cotangent read and the input's gradient written; the taps and the bias
once a pass.  Recomputation is never counted, and nothing here looks at
how the program computes anything (tiles, the carried tail, a slice of
the input projection's output read in place).
"""
from chipbench import flops_granite, flops_lfm2
from chipbench.flops_lfm2 import width


def conv_train_work(cfg, tokens, layers, c_in, c_out, taps, bias):
    """(FLOPs, HBM bytes) of *layers* convolutions over *tokens* tokens:
    *c_in* channels read, *c_out* written, *taps* taps a channel of the
    output, a bias a channel or none."""
    w = width(cfg)
    params = (taps + bool(bias)) * c_out * w
    forward = (c_in + c_out) * tokens * w + params
    backward = (2 * c_in + c_out) * tokens * w + params
    flops = 3 * 2 * taps * c_out * tokens
    return layers * flops, layers * (forward + backward)


def granite_conv_train_work(cfg, tokens):
    """Every Mamba-2 layer's convolution over x' | B | C: 4,352 channels
    in and out at the published widths, ``mamba_d_conv`` taps, the
    bias ``mamba_conv_bias`` says."""
    layers = sum(kind == "mamba" for kind in flops_granite.kinds(cfg))
    channels = flops_granite.mamba_widths(cfg)[1]
    return conv_train_work(cfg, tokens, layers, channels, channels,
                           cfg["mamba_d_conv"], cfg["mamba_conv_bias"])


def lfm2_conv_train_work(cfg, tokens):
    """Every conv layer's gated short convolution: (Bg, Cg, u), 3 x
    ``hidden_size`` channels, in; ``conv_L_cache`` taps over Bg * u and
    Cg's gate, ``hidden_size`` channels, out; the bias ``conv_bias``
    says."""
    layers = sum(op == "conv" for op, _ in flops_lfm2.kinds(cfg))
    h = cfg["hidden_size"]
    return conv_train_work(cfg, tokens, layers, 3 * h, h,
                           cfg["conv_L_cache"], cfg["conv_bias"])
