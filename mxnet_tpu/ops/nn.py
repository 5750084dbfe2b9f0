"""Neural-network layer ops (the legacy OperatorProperty corpus, TPU-native).

Reference analogue: ``src/operator/{convolution,pooling,batch_norm,activation,
dropout,fully_connected,softmax_output,rnn,...}-inl.h`` (SURVEY §2.2 "NN
layers").  Re-design notes:

- Convolution/Deconvolution lower to ``lax.conv_general_dilated`` (MXU path);
  there is no im2col, no cuDNN algo registry — XLA autotunes tiling.
- Pooling is ``lax.reduce_window``.
- BatchNorm is a pure function returning updated moving stats as extra
  outputs (``aux_updates``) instead of mutating aux buffers in a kernel.
  In training an activation narrower than float32 (bfloat16, float16)
  goes through ``_bn_train``: float32 moments in one pass (``sum(x)`` and
  ``sum(x*x)`` as siblings of one reduction, which XLA puts into the
  kernel that produces ``x``) and a hand-derived VJP (``sum(dy)`` and
  ``sum(dy*xhat)`` together, ``xhat`` recomputed from the input): four
  per-channel reductions in two rounds.  ``jnp.mean`` + ``jnp.var`` under
  ``jax.vjp`` compiles to seven in four, two of them passes of their own
  over the activation (PERF.md section 6, PR 28).  float32 and float64
  activations keep that form: with no wider type to accumulate in, one
  pass would cancel in the data's own precision.
- Dropout takes an explicit PRNG key (``needs_rng``) so it is jit-safe.
- The fused RNN op is a ``lax.scan`` over time — the XLA-native equivalent of
  cuDNN's fused RNN (``cudnn_rnn-inl.h``).
- Loss-layer ops (SoftmaxOutput & regression outputs) keep MXNet's *semantic*
  gradients via ``custom_vjp`` (backward ignores head-grad and uses labels,
  reference ``softmax_output-inl.h``).

Layout: NCHW / TNC defaults, matching the reference's Python API surface.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry as _tel
from .registry import register
from ..base import dtype_np


def _tup(v, n=None):
    if isinstance(v, int):
        v = (v,) * (n or 1)
    return tuple(v)


# --- FullyConnected ---------------------------------------------------------
@register("FullyConnected")
def _fully_connected(data, weight, *maybe_bias, num_hidden=None, no_bias=False,
                     flatten=True, **kw):
    x = data.reshape((data.shape[0], -1)) if flatten else data
    out = jnp.dot(x, weight.T)
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0]
    return out


# --- Convolution family -----------------------------------------------------
def _conv_dims(kernel):
    nd = len(kernel)
    spat = "DHW"[3 - nd:]
    return ("NC" + spat, "OI" + spat, "NC" + spat)


@register("Convolution", aliases=["Convolution_v1"])
def _convolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                 pad=(), num_filter=1, num_group=1, no_bias=False,
                 workspace=1024, cudnn_tune=None, cudnn_off=False, layout=None, **kw):
    nd = len(kernel)
    stride = _tup(stride or (1,) * nd, nd)
    dilate = _tup(dilate or (1,) * nd, nd)
    pad = _tup(pad or (0,) * nd, nd)
    # bf16 in/out: the MXU accumulates partial products in f32 regardless
    # and rounds once at the output, so no preferred_element_type override
    # (which would make the conv transpose rule see an f32 cotangent
    # against bf16 operands and fail under AD)
    out = lax.conv_general_dilated(
        data, weight, window_strides=stride,
        padding=[(p, p) for p in pad],
        rhs_dilation=dilate, feature_group_count=int(num_group),
        dimension_numbers=_conv_dims(kernel))
    if not no_bias and maybe_bias:
        b = maybe_bias[0].reshape((1, -1) + (1,) * nd)
        out = out + b
    return out


@register("Deconvolution")
def _deconvolution(data, weight, *maybe_bias, kernel=(), stride=(), dilate=(),
                   pad=(), adj=(), target_shape=(), num_filter=1, num_group=1,
                   no_bias=True, workspace=512, cudnn_tune=None, cudnn_off=False,
                   layout=None, **kw):
    nd = len(kernel)
    stride = _tup(stride or (1,) * nd, nd)
    pad = _tup(pad or (0,) * nd, nd)
    adj = _tup(adj or (0,) * nd, nd)
    g = int(num_group)
    c = weight.shape[0]
    f = weight.shape[1] * g
    # weight (C, F/g, *k) -> (F, C/g, *k), spatially flipped
    w = weight.reshape((g, c // g, f // g) + tuple(kernel))
    w = jnp.swapaxes(w, 1, 2).reshape((f, c // g) + tuple(kernel))
    w = jnp.flip(w, axis=tuple(range(2, 2 + nd)))
    padding = [(k - 1 - p, k - 1 - p + a) for k, p, a in zip(kernel, pad, adj)]
    out = lax.conv_general_dilated(
        data, w, window_strides=(1,) * nd, padding=padding,
        lhs_dilation=stride, feature_group_count=g,
        dimension_numbers=_conv_dims(kernel))
    if not no_bias and maybe_bias:
        out = out + maybe_bias[0].reshape((1, -1) + (1,) * nd)
    return out


# --- Pooling ----------------------------------------------------------------
@register("Pooling", aliases=["Pooling_v1"])
def _pooling(data, kernel=(), pool_type="max", global_pool=False, stride=(),
             pad=(), pooling_convention="valid", count_include_pad=True,
             cudnn_off=False, p_value=2, layout=None, **kw):
    nd = data.ndim - 2
    if global_pool:
        kernel = data.shape[2:]
        stride = (1,) * nd
        pad = (0,) * nd
    kernel = _tup(kernel, nd)
    stride = _tup(stride or (1,) * nd, nd)
    pad = _tup(pad or (0,) * nd, nd)
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode: pad on the high side so the last partial window counts
        extra = []
        for i in range(nd):
            inp = data.shape[2 + i]
            out_sz = int(np.ceil((inp + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - inp - 2 * pad[i]
            extra.append(max(0, need))
        padding = ((0, 0), (0, 0)) + tuple((p, p + e) for p, e in zip(pad, extra))
    else:
        padding = ((0, 0), (0, 0)) + tuple((p, p) for p in pad)
    if pool_type == "max":
        init = (-np.inf if jnp.issubdtype(data.dtype, jnp.floating)
                else np.iinfo(np.dtype(data.dtype)).min)
        return lax.reduce_window(data, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, 0.0 if jnp.issubdtype(data.dtype, jnp.floating) else 0,
                              lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            return s / float(np.prod(kernel))
        ones = jnp.ones_like(data)
        cnt = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / cnt
    if pool_type == "lp":
        p = float(p_value)
        s = lax.reduce_window(jnp.abs(data) ** p, 0.0, lax.add,
                              window, strides, padding)
        return s ** (1.0 / p)
    raise ValueError("unknown pool_type %s" % pool_type)


@register("UpSampling")
def _upsampling(*args, scale=1, sample_type="nearest", num_filter=0,
                multi_input_mode="concat", num_args=1, workspace=512, **kw):
    data = args[0]
    s = int(scale)
    if sample_type == "nearest":
        outs = []
        for a in args:
            o = jnp.repeat(jnp.repeat(a, s, axis=2), s, axis=3)
            outs.append(o)
        if len(outs) == 1:
            return outs[0]
        if multi_input_mode == "sum":
            return sum(outs)
        return jnp.concatenate(outs, axis=1)
    # bilinear: args = (data, weight) in reference; use jax.image.resize
    n, c, h, w = data.shape
    return jax.image.resize(data, (n, c, h * s, w * s), method="bilinear")


# --- BatchNorm --------------------------------------------------------------
def _bn_axes(data, ax):
    """(reduced axes, broadcast shape of a per-channel vector, elements a
    channel)."""
    return tuple(i for i in range(data.ndim) if i != ax), \
        tuple(-1 if i == ax else 1 for i in range(data.ndim)), \
        data.size // data.shape[ax]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _bn_train(data, g, beta, ax, eps):
    """Training BatchNorm over every axis but *ax*: (out, batch mean,
    batch variance).  *g* and *beta* come in the accumulation dtype
    (float32; float64 for float64 data), the statistics leave in it.  The
    statistics are outputs for the moving averages only: the VJP sends
    nothing back through them."""
    return _bn_train_fwd(data, g, beta, ax, eps)[0]


def _bn_train_fwd(data, g, beta, ax, eps):
    red, shape, n = _bn_axes(data, ax)
    x = data.astype(g.dtype)
    # one read of the activation: the two sums are siblings of one fusion
    s1, s2 = jnp.sum(x, axis=red), jnp.sum(x * x, axis=red)
    mean = s1 / n
    var = jnp.maximum(s2 / n - mean * mean, 0)
    inv = lax.rsqrt(var + eps)
    out = (x - mean.reshape(shape)) * (inv * g).reshape(shape) \
        + beta.reshape(shape)
    return (out.astype(data.dtype), mean, var), (data, mean, inv, g)


def _bn_train_bwd(ax, eps, res, cts):
    data, mean, inv, g = res
    red, shape, n = _bn_axes(data, ax)
    dy = cts[0].astype(g.dtype)
    xhat = (data.astype(g.dtype) - mean.reshape(shape)) * inv.reshape(shape)
    # again one read of cotangent and activation for both sums
    s_dy, s_dyx = jnp.sum(dy, axis=red), jnp.sum(dy * xhat, axis=red)
    dx = (g * inv).reshape(shape) * (
        dy - (s_dy / n).reshape(shape) - xhat * (s_dyx / n).reshape(shape))
    return dx.astype(data.dtype), s_dyx, s_dy


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


def _bn_moving(moving, batch, momentum):
    return (moving * momentum + batch * (1 - momentum)).astype(moving.dtype)


@register("BatchNorm", aliases=["BatchNorm_v1", "CuDNNBatchNorm"],
          num_outputs=3, num_visible_outputs=1,
          nondiff_inputs=(3, 4), aux_updates={3: 1, 4: 2}, takes_mode=True)
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
                momentum=0.9, fix_gamma=True, use_global_stats=False,
                output_mean_var=False, axis=1, cudnn_off=False,
                train_mode=False, **kw):
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    g = jnp.ones_like(gamma) if fix_gamma else gamma
    if train_mode and not use_global_stats:
        acc = jnp.promote_types(data.dtype, jnp.float32)
        if data.dtype != acc:
            # an activation narrower than its accumulator: what
            # E[x^2] - E[x]^2 cancels in float32 is below the activation's
            # own rounding, so one pass is enough
            _tel.bump("batchnorm_onepass_traced")
            out, mean, var = _bn_train(data, g.astype(acc),
                                       beta.astype(acc), ax, float(eps))
            return out, _bn_moving(moving_mean, mean, momentum), \
                _bn_moving(moving_var, var, momentum)
        mean = jnp.mean(data, axis=red)
        var = jnp.var(data, axis=red)
        new_mm = _bn_moving(moving_mean, mean, momentum)
        new_mv = _bn_moving(moving_var, var, momentum)
    else:
        mean, var = moving_mean, moving_var
        new_mm, new_mv = moving_mean, moving_var
    inv = lax.rsqrt(var + eps)
    out = (data - mean.reshape(shape)) * inv.reshape(shape) * g.reshape(shape) \
        + beta.reshape(shape)
    return out, new_mm, new_mv


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3, **kw):
    red = tuple(range(2, data.ndim))
    mean = jnp.mean(data, axis=red, keepdims=True)
    var = jnp.var(data, axis=red, keepdims=True)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    return ((data - mean) * lax.rsqrt(var + eps)) * gamma.reshape(shape) \
        + beta.reshape(shape)


@register("LayerNorm")
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **kw):
    mean = jnp.mean(data, axis=axis, keepdims=True)
    var = jnp.var(data, axis=axis, keepdims=True)
    out = (data - mean) * lax.rsqrt(var + eps)
    shape = [1] * data.ndim
    shape[axis % data.ndim] = data.shape[axis % data.ndim]
    return out * gamma.reshape(shape) + beta.reshape(shape)


@register("L2Normalization")
def _l2_normalization(data, eps=1e-10, mode="instance", **kw):
    if mode == "instance":
        red = tuple(range(1, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    elif mode == "channel":
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=1, keepdims=True) + eps)
    else:  # spatial
        red = tuple(range(2, data.ndim))
        n = jnp.sqrt(jnp.sum(jnp.square(data), axis=red, keepdims=True) + eps)
    return data / n


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    sq = jnp.square(data)
    half = int(nsize) // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    windows = sum(padded[:, i:i + data.shape[1]] for i in range(int(nsize)))
    return data / jnp.power(knorm + (alpha / nsize) * windows, beta)


# --- Activations ------------------------------------------------------------
@register("Activation")
def _activation(data, act_type="relu", **kw):
    if act_type == "relu":
        return jnp.maximum(data, 0)
    if act_type == "sigmoid":
        return jax.nn.sigmoid(data)
    if act_type == "tanh":
        return jnp.tanh(data)
    if act_type == "softrelu":
        return jax.nn.softplus(data)
    if act_type == "softsign":
        return jax.nn.soft_sign(data)
    raise ValueError("unknown act_type %s" % act_type)


@register("LeakyReLU", needs_rng=True, takes_mode=True)
def _leaky_relu(data, *maybe_gamma, act_type="leaky", slope=0.25,
                lower_bound=0.125, upper_bound=0.334, rng=None,
                train_mode=False, **kw):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, slope * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, slope * (jnp.exp(data) - 1))
    if act_type == "selu":
        return 1.0507009873554805 * jax.nn.elu(data, 1.6732632423543772)
    if act_type == "prelu":
        gamma = maybe_gamma[0]
        shape = [1] * data.ndim
        if gamma.size > 1 and data.ndim > 1:
            shape[1] = gamma.size
        return jnp.where(data >= 0, data, gamma.reshape(shape) * data)
    if act_type == "rrelu":
        if train_mode and rng is not None:
            lo, hi = float(lower_bound), float(upper_bound)
            r = jax.random.uniform(rng, data.shape, data.dtype, lo, hi)
            return jnp.where(data >= 0, data, r * data)
        s = (float(lower_bound) + float(upper_bound)) / 2.0
        return jnp.where(data >= 0, data, s * data)
    raise ValueError("unknown act_type %s" % act_type)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance", **kw):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


# --- Dropout ----------------------------------------------------------------
@register("Dropout", needs_rng=True, takes_mode=True)
def _dropout(data, p=0.5, mode="training", axes=(), rng=None,
              train_mode=False, cudnn_off=False, **kw):
    if (not train_mode and mode != "always") or p <= 0 or rng is None:
        return data
    shape = list(data.shape)
    for a in axes or ():
        shape[a] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(rng, keep, tuple(shape)).astype(data.dtype) / keep
    return data * mask


# --- Loss-layer ops with semantic gradients ---------------------------------
def _softmax_fwd(data, multi_output=False, preserve_shape=False, temperature=None):
    if multi_output:
        return jax.nn.softmax(data, axis=1)
    if preserve_shape:
        return jax.nn.softmax(data, axis=-1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


def _softmax_output_bwd(out_grads, inputs, outputs, attrs):
    data, label = inputs[0], inputs[1]
    out = outputs[0]
    grad_scale = attrs.get("grad_scale", 1.0)
    ignore_label = attrs.get("ignore_label", -1.0)
    use_ignore = attrs.get("use_ignore", False)
    multi_output = attrs.get("multi_output", False)
    normalization = attrs.get("normalization", "null")
    smooth_alpha = attrs.get("smooth_alpha", 0.0)
    if multi_output:
        # data (N, C, ...) label (N, ...)
        c = data.shape[1]
        lab = label.astype(jnp.int32)
        oh = jnp.moveaxis(jax.nn.one_hot(lab, c, dtype=data.dtype), -1, 1)
        if smooth_alpha:
            oh = oh * (1 - smooth_alpha) + smooth_alpha / (c - 1) * (1 - oh)
        grad = out - oh
        valid = jnp.ones(lab.shape, data.dtype)
        if use_ignore:
            valid = (lab != int(ignore_label)).astype(data.dtype)
            grad = grad * valid[:, None]
        norm = 1.0
        if normalization == "valid":
            norm = jnp.maximum(jnp.sum(valid), 1.0)
        elif normalization == "batch":
            norm = float(data.shape[0])
        return (grad * (grad_scale / norm), jnp.zeros_like(label))
    if label.ndim == data.ndim:  # one-hot/dense label
        grad = out - label
        norm = float(data.shape[0]) if normalization == "batch" else 1.0
        return (grad * (grad_scale / norm), jnp.zeros_like(label))
    c = data.shape[-1]
    lab = label.astype(jnp.int32)
    oh = jax.nn.one_hot(lab, c, dtype=data.dtype)
    if smooth_alpha:
        oh = oh * (1 - smooth_alpha) + smooth_alpha / (c - 1) * (1 - oh)
    grad = out - oh
    valid = jnp.ones(lab.shape, data.dtype)
    if use_ignore:
        valid = (lab != int(ignore_label)).astype(data.dtype)
        grad = grad * valid[..., None]
    norm = 1.0
    if normalization == "valid":
        norm = jnp.maximum(jnp.sum(valid), 1.0)
    elif normalization == "batch":
        norm = float(data.shape[0])
    return (grad * (grad_scale / norm), jnp.zeros_like(label))


@register("SoftmaxOutput", aliases=["Softmax"], nondiff_inputs=(1,),
          custom_vjp=_softmax_output_bwd)
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1.0,
                    multi_output=False, use_ignore=False, preserve_shape=False,
                    normalization="null", out_grad=False, smooth_alpha=0.0, **kw):
    return _softmax_fwd(data, multi_output, preserve_shape)


def _linreg_bwd(out_grads, inputs, outputs, attrs):
    data, label = inputs
    gs = attrs.get("grad_scale", 1.0)
    return ((outputs[0] - label.reshape(data.shape)) * gs, jnp.zeros_like(label))


@register("LinearRegressionOutput", nondiff_inputs=(1,), custom_vjp=_linreg_bwd)
def _lin_reg_output(data, label, grad_scale=1.0, **kw):
    return data


def _maereg_bwd(out_grads, inputs, outputs, attrs):
    data, label = inputs
    gs = attrs.get("grad_scale", 1.0)
    return (jnp.sign(data - label.reshape(data.shape)) * gs, jnp.zeros_like(label))


@register("MAERegressionOutput", nondiff_inputs=(1,), custom_vjp=_maereg_bwd)
def _mae_reg_output(data, label, grad_scale=1.0, **kw):
    return data


def _logreg_bwd(out_grads, inputs, outputs, attrs):
    data, label = inputs
    gs = attrs.get("grad_scale", 1.0)
    return ((outputs[0] - label.reshape(data.shape)) * gs, jnp.zeros_like(label))


@register("LogisticRegressionOutput", nondiff_inputs=(1,), custom_vjp=_logreg_bwd)
def _log_reg_output(data, label, grad_scale=1.0, **kw):
    return jax.nn.sigmoid(data)


def _svm_bwd(out_grads, inputs, outputs, attrs):
    data, label = inputs
    margin = attrs.get("margin", 1.0)
    reg = attrs.get("regularization_coefficient", 1.0)
    use_linear = attrs.get("use_linear", False)
    c = data.shape[-1]
    lab = label.astype(jnp.int32)
    oh = jax.nn.one_hot(lab, c, dtype=data.dtype)
    score_y = jnp.take_along_axis(data, lab[..., None], axis=-1)
    viol = (margin - (score_y - data)) > 0
    viol = viol.astype(data.dtype) * (1 - oh)
    if use_linear:
        grad = reg * (viol - oh * jnp.sum(viol, axis=-1, keepdims=True))
    else:
        dist = (margin - (score_y - data)) * (1 - oh)
        grad = reg * 2 * jnp.maximum(dist, 0)
        grad = grad - oh * jnp.sum(grad, axis=-1, keepdims=True)
    return (grad, jnp.zeros_like(label))


@register("SVMOutput", nondiff_inputs=(1,), custom_vjp=_svm_bwd)
def _svm_output(data, label, margin=1.0, regularization_coefficient=1.0,
                use_linear=False, **kw):
    return data


@register("softmax_cross_entropy", nondiff_inputs=(1,))
def _softmax_cross_entropy(data, label, **kw):
    logp = jax.nn.log_softmax(data, axis=-1)
    lab = label.astype(jnp.int32)
    return -jnp.sum(jnp.take_along_axis(logp, lab[..., None], axis=-1))


@register("MakeLoss", custom_vjp=lambda og, i, o, a:
          (jnp.ones_like(i[0]) * a.get("grad_scale", 1.0),))
def _make_loss_layer(data, grad_scale=1.0, valid_thresh=0.0,
                     normalization="null", **kw):
    if normalization == "batch":
        return data / data.shape[0]
    if normalization == "valid":
        valid = jnp.sum((data > valid_thresh).astype(data.dtype))
        return data / jnp.maximum(valid, 1.0)
    return data


# --- Sequence ops -----------------------------------------------------------
@register("SequenceMask")
def _sequence_mask(data, *maybe_len, use_sequence_length=False, value=0.0,
                   axis=0, **kw):
    if not use_sequence_length or not maybe_len:
        return data
    seq_len = maybe_len[0]
    t = data.shape[axis]
    pos = jnp.arange(t)
    if axis == 0:
        mask = pos[:, None] < seq_len[None, :].astype(pos.dtype)
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    else:
        mask = pos[None, :] < seq_len[:, None].astype(pos.dtype)
        mask = mask.reshape(mask.shape + (1,) * (data.ndim - 2))
    return jnp.where(mask, data, jnp.asarray(value, data.dtype))


@register("SequenceLast", nondiff_inputs=(1,))
def _sequence_last(data, *maybe_len, use_sequence_length=False, axis=0, **kw):
    if not use_sequence_length or not maybe_len:
        return jnp.take(data, data.shape[axis] - 1, axis=axis)
    seq_len = maybe_len[0].astype(jnp.int32) - 1
    if axis == 0:
        return data[seq_len, jnp.arange(data.shape[1])]
    return data[jnp.arange(data.shape[0]), seq_len]


@register("SequenceReverse")
def _sequence_reverse(data, *maybe_len, use_sequence_length=False, axis=0, **kw):
    if not use_sequence_length or not maybe_len:
        return jnp.flip(data, axis=0)
    seq_len = maybe_len[0].astype(jnp.int32)
    t = data.shape[0]
    pos = jnp.arange(t)[:, None]
    rev = seq_len[None, :] - 1 - pos
    idx = jnp.where(rev >= 0, rev, pos)
    return jnp.take_along_axis(
        data, idx.reshape(idx.shape + (1,) * (data.ndim - 2)).astype(jnp.int32), axis=0)


# --- Fused RNN (lax.scan; the XLA-native cuDNN-RNN equivalent) --------------
def _gates(mode):
    return {"rnn_relu": 1, "rnn_tanh": 1, "lstm": 4, "gru": 3}[mode]


def rnn_param_size(num_layers, input_size, state_size, mode, bidirectional=False):
    """Total packed parameter count; layout documented in _rnn_unpack."""
    g = _gates(mode)
    d = 2 if bidirectional else 1
    size = 0
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else state_size * d
        size += d * (g * state_size * in_sz + g * state_size * state_size
                     + 2 * g * state_size)
    return size


def _rnn_unpack(params, num_layers, input_size, state_size, mode, bidirectional):
    """Packed layout: per layer, per direction: i2h_W (G*H, in), h2h_W (G*H, H),
    i2h_b (G*H), h2h_b (G*H).  Gate order: LSTM i,f,g,o; GRU r,z,n."""
    g = _gates(mode)
    d = 2 if bidirectional else 1
    h = state_size
    off = 0
    layers = []
    for layer in range(num_layers):
        in_sz = input_size if layer == 0 else h * d
        dirs = []
        for _ in range(d):
            wi = params[off:off + g * h * in_sz].reshape(g * h, in_sz); off += g * h * in_sz
            wh = params[off:off + g * h * h].reshape(g * h, h); off += g * h * h
            bi = params[off:off + g * h]; off += g * h
            bh = params[off:off + g * h]; off += g * h
            dirs.append((wi, wh, bi, bh))
        layers.append(dirs)
    return layers


def _rnn_cell_step(mode, h):
    def step(carry, x_t, wi, wh, bi, bh):
        if mode in ("rnn_relu", "rnn_tanh"):
            hp = carry[0]
            pre = x_t @ wi.T + bi + hp @ wh.T + bh
            hn = jnp.maximum(pre, 0) if mode == "rnn_relu" else jnp.tanh(pre)
            return (hn,), hn
        if mode == "lstm":
            hp, cp = carry
            pre = x_t @ wi.T + bi + hp @ wh.T + bh
            i, f, gg, o = jnp.split(pre, 4, axis=-1)
            i, f, o = jax.nn.sigmoid(i), jax.nn.sigmoid(f), jax.nn.sigmoid(o)
            gg = jnp.tanh(gg)
            cn = f * cp + i * gg
            hn = o * jnp.tanh(cn)
            return (hn, cn), hn
        # gru
        hp = carry[0]
        xi = x_t @ wi.T + bi
        hh = hp @ wh.T + bh
        xr, xz, xn = jnp.split(xi, 3, axis=-1)
        hr, hz, hn_ = jnp.split(hh, 3, axis=-1)
        r = jax.nn.sigmoid(xr + hr)
        z = jax.nn.sigmoid(xz + hz)
        n = jnp.tanh(xn + r * hn_)
        hn = (1 - z) * n + z * hp
        return (hn,), hn
    return step


def _rnn_nout(attrs):
    if not attrs.get("state_outputs", False):
        return 1
    return 3 if attrs.get("mode", "lstm") == "lstm" else 2


@register("RNN", num_outputs=_rnn_nout, needs_rng=True, takes_mode=True)
def _rnn(data, parameters, state, *maybe_cell, state_size=0, num_layers=1,
         bidirectional=False, mode="lstm", p=0.0, state_outputs=False,
         rng=None, train_mode=False, lstm_state_clip_min=None,
         lstm_state_clip_max=None, projection_size=None, **kw):
    """Fused multi-layer RNN. data: (T, N, C); state: (L*D, N, H)."""
    t, n, input_size = data.shape
    h = int(state_size)
    d = 2 if bidirectional else 1
    is_lstm = mode == "lstm"
    cell0 = maybe_cell[0] if is_lstm and maybe_cell else None
    layers = _rnn_unpack(parameters, int(num_layers), input_size, h, mode,
                         bidirectional)
    step = _rnn_cell_step(mode, h)
    x = data
    out_h, out_c = [], []
    for li, dirs in enumerate(layers):
        dir_outs = []
        for di, (wi, wh, bi, bh) in enumerate(dirs):
            idx = li * d + di
            h0 = state[idx]
            carry = (h0, cell0[idx]) if is_lstm else (h0,)
            seq = jnp.flip(x, axis=0) if di == 1 else x

            def scan_fn(c, x_t, wi=wi, wh=wh, bi=bi, bh=bh):
                return step(c, x_t, wi, wh, bi, bh)
            carry, ys = lax.scan(scan_fn, carry, seq)
            if di == 1:
                ys = jnp.flip(ys, axis=0)
            dir_outs.append(ys)
            out_h.append(carry[0])
            if is_lstm:
                out_c.append(carry[1])
        x = dir_outs[0] if d == 1 else jnp.concatenate(dir_outs, axis=-1)
        if p > 0 and train_mode and rng is not None and li < len(layers) - 1:
            rng, sub = jax.random.split(rng)
            keep = 1.0 - p
            x = x * jax.random.bernoulli(sub, keep, x.shape).astype(x.dtype) / keep
    outs = [x]
    if state_outputs:
        outs.append(jnp.stack(out_h, axis=0))
        if is_lstm:
            outs.append(jnp.stack(out_c, axis=0))
    return tuple(outs) if len(outs) > 1 else outs[0]


# --- Spatial/geometry ops ---------------------------------------------------
@register("GridGenerator")
def _grid_generator(data, transform_type="affine", target_shape=(0, 0), **kw):
    h, w = int(target_shape[0]), int(target_shape[1])
    if transform_type == "affine":
        n = data.shape[0]
        theta = data.reshape(n, 2, 3)
        ys = jnp.linspace(-1, 1, h)
        xs = jnp.linspace(-1, 1, w)
        gx, gy = jnp.meshgrid(xs, ys)
        ones = jnp.ones_like(gx)
        grid = jnp.stack([gx, gy, ones], axis=0).reshape(3, -1)
        out = jnp.einsum("nij,jk->nik", theta, grid.astype(data.dtype))
        return out.reshape(n, 2, h, w)
    return data  # warp type: data is already the flow grid


def _bilinear_sample(data, grid):
    """data (N,C,H,W), grid (N,2,Ho,Wo) in [-1,1] (x, y)."""
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1) * (w - 1) / 2
    gy = (grid[:, 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    x1 = x0 + 1; y1 = y0 + 1
    wx1 = gx - x0; wy1 = gy - y0
    wx0 = 1 - wx1; wy0 = 1 - wy1

    def gather(yy, xx):
        yi = jnp.clip(yy, 0, h - 1).astype(jnp.int32)
        xi = jnp.clip(xx, 0, w - 1).astype(jnp.int32)
        valid = ((yy >= 0) & (yy <= h - 1) & (xx >= 0) & (xx <= w - 1))
        batch = jnp.arange(n).reshape(n, 1, 1)
        vals = data[batch, :, yi, xi]  # (N,Ho,Wo,C)
        vals = jnp.moveaxis(vals, -1, 1)
        return vals * valid[:, None].astype(data.dtype)

    out = (gather(y0, x0) * (wy0 * wx0)[:, None]
           + gather(y0, x1) * (wy0 * wx1)[:, None]
           + gather(y1, x0) * (wy1 * wx0)[:, None]
           + gather(y1, x1) * (wy1 * wx1)[:, None])
    return out


@register("BilinearSampler")
def _bilinear_sampler(data, grid, cudnn_off=False, **kw):
    return _bilinear_sample(data, grid)


@register("SpatialTransformer")
def _spatial_transformer(data, loc, target_shape=(0, 0),
                         transform_type="affine", sampler_type="bilinear",
                         cudnn_off=False, **kw):
    grid = _grid_generator(loc, transform_type, target_shape)
    return _bilinear_sample(data, grid)


@register("Crop", nondiff_inputs=(1,))
def _crop_op(*args, offset=(0, 0), h_w=(0, 0), center_crop=False,
             num_args=1, **kw):
    data = args[0]
    if len(args) > 1:
        th, tw = args[1].shape[2], args[1].shape[3]
    else:
        th, tw = int(h_w[0]), int(h_w[1])
    if center_crop:
        oy = (data.shape[2] - th) // 2
        ox = (data.shape[3] - tw) // 2
    else:
        oy, ox = int(offset[0]), int(offset[1])
    return data[:, :, oy:oy + th, ox:ox + tw]


@register("ROIPooling", nondiff_inputs=(1,))
def _roi_pooling(data, rois, pooled_size=(1, 1), spatial_scale=1.0, **kw):
    """ROI max pooling via per-bin masked max (XLA-friendly, no dynamic shapes)."""
    n, c, h, w = data.shape
    ph, pw = int(pooled_size[0]), int(pooled_size[1])

    ys = jnp.arange(h, dtype=data.dtype)
    xs = jnp.arange(w, dtype=data.dtype)

    def one_roi(roi):
        b = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * spatial_scale)
        y1 = jnp.round(roi[2] * spatial_scale)
        x2 = jnp.round(roi[3] * spatial_scale)
        y2 = jnp.round(roi[4] * spatial_scale)
        rh = jnp.maximum(y2 - y1 + 1, 1.0)
        rw = jnp.maximum(x2 - x1 + 1, 1.0)
        bh, bw = rh / ph, rw / pw
        img = data[b]  # (C,H,W)

        def bin_val(i, j):
            ys0 = y1 + jnp.floor(i * bh)
            ys1 = y1 + jnp.ceil((i + 1) * bh)
            xs0 = x1 + jnp.floor(j * bw)
            xs1 = x1 + jnp.ceil((j + 1) * bw)
            ymask = (ys >= ys0) & (ys < jnp.maximum(ys1, ys0 + 1)) & (ys <= y2)
            xmask = (xs >= xs0) & (xs < jnp.maximum(xs1, xs0 + 1)) & (xs <= x2)
            mask = ymask[:, None] & xmask[None, :]
            masked = jnp.where(mask[None], img, -jnp.inf)
            v = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(v), v, 0.0)

        ii, jj = jnp.meshgrid(jnp.arange(ph, dtype=data.dtype),
                              jnp.arange(pw, dtype=data.dtype), indexing="ij")
        vals = jax.vmap(jax.vmap(bin_val))(ii, jj)  # (ph,pw,C)
        return jnp.moveaxis(vals, -1, 0)

    return jax.vmap(one_roi)(rois)


@register("Correlation")
def _correlation(data1, data2, kernel_size=1, max_displacement=1, stride1=1,
                 stride2=1, pad_size=0, is_multiply=True, **kw):
    n, c, h, w = data1.shape
    pad = int(pad_size)
    d1 = jnp.pad(data1, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    d2 = jnp.pad(data2, ((0, 0), (0, 0), (pad, pad), (pad, pad)))
    md = int(max_displacement)
    s2 = int(stride2)
    disps = range(-md, md + 1, s2)
    outs = []
    hh, ww = d1.shape[2], d1.shape[3]
    for dy in disps:
        for dx in disps:
            shifted = jnp.roll(d2, (-dy, -dx), axis=(2, 3))
            if is_multiply:
                prod = jnp.mean(d1 * shifted, axis=1)
            else:
                prod = jnp.mean(jnp.abs(d1 - shifted), axis=1)
            outs.append(prod)
    out = jnp.stack(outs, axis=1)
    return out[:, :, pad:hh - pad, pad:ww - pad]
