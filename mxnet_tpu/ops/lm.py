"""Language-model ops: ``RMSNorm``, ``_contrib_RotaryEmbedding``,
``_contrib_PowerRetention``, ``_contrib_CausalAttention`` (grouped-query,
with or without a window), ``_contrib_ShortConv`` (gated) and
``_contrib_CausalConv1D`` (biased, with an activation) over one taps
helper, ``_contrib_StateSpaceScan`` (Mamba-2's selective scan) and the
blocked softmax-cross-entropy head ``_contrib_BlockedSoftmaxCE`` (the
sparse-expert layer is ``ops/moe.py``).

No reference counterpart: the reference's op corpus predates all of them.
They are registered like every other op so that a language model is a
``Symbol`` and trains through ``Module.fit``.  Every op accumulates in
float32 whatever its operands' dtype (``docs/LM_OPS.md`` has the
equations).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _tel
from . import remat as _remat
from .registry import register


def rms_norm(x, gamma, axis=-1, eps=1e-6):
    """x / sqrt(mean(x^2) + eps) in float32, cast back, times gamma."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis,
                   keepdims=True)
    shape = [1] * x.ndim
    shape[axis % x.ndim] = x.shape[axis % x.ndim]
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * \
        gamma.reshape(shape)


@register("RMSNorm")
def _rms_norm(data, gamma, axis=-1, eps=1e-6, **kw):
    """Root-mean-square normalisation over ``axis`` with a learned gain."""
    return rms_norm(data, gamma, int(axis), float(eps))


@register("_contrib_RotaryEmbedding", aliases=["RotaryEmbedding"])
def _rotary_embedding(data, base=10000.0, offset=0, **kw):
    """Rotary position embedding, rotate-half convention, on
    [batch, seq, heads, dim]: position p = offset + index along axis 1,
    angle p * base^(-2i/dim) for the pair (i, i + dim/2)."""
    s, dim = data.shape[1], data.shape[-1]
    half = dim // 2
    inv = float(base) ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / dim)
    ang = (jnp.arange(s, dtype=jnp.float32) + float(offset))[:, None] * inv
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x = data.astype(jnp.float32)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                          axis=-1)
    return out.astype(data.dtype)


@register("_contrib_PowerRetention", aliases=["PowerRetention"])
def _power_retention(query, key, value, log_gate, degree=2, chunk=128,
                     eps=1e-6, **kw):
    """Causal power retention in the chunked state form: query
    [B, S, Hq, d], key/value [B, S, Hkv, d], log_gate [B, S, Hkv] (the
    float32 log of a gate in (0, 1]) -> [B, S, Hq, d].  The forward is
    the Pallas kernel on a TPU (head size and chunk multiples of 128)
    and the same algorithm in ``jnp`` elsewhere."""
    from .pallas_kernels import power_retention
    if int(degree) != 2:
        raise ValueError("_contrib_PowerRetention: degree %s is not "
                         "implemented (2 is)" % degree)
    chunk = int(chunk)
    kernel = jax.default_backend() == "tpu" and chunk % 128 == 0 and \
        query.shape[-1] % 128 == 0 and value.shape[-1] % 128 == 0
    _tel.bump("power_retention_traced")
    _tel.bump("power_retention_chunks", -(-query.shape[1] // chunk))
    return power_retention(query, key, value, log_gate.astype(jnp.float32),
                           chunk, float(eps), kernel)


def _masked_softmax_attention(q, k, v, scale, causal, window=None):
    """softmax(q k^T scale) v with the scores whole, float32 statistics:
    q [B, Hq, S, d], k and v [B, Hkv, S, d] -> [B, Hq, S, d].  *window*
    (causal only) keeps the keys q_pos - window < k_pos <= q_pos."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    qg = q.reshape(b, hkv, hq // hkv, s, d)
    score = jnp.einsum("bhgqd,bhkd->bhgqk", qg, k,
                       preferred_element_type=jnp.float32) * scale
    if causal:
        behind = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
        keep = behind >= 0
        if window is not None:
            keep = keep & (behind < window)
        score = jnp.where(keep, score, -jnp.inf)
    p = jax.nn.softmax(score, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(q.shape).astype(q.dtype)


_ATTENTION_TILE = 512      # square tiles of the Pallas forward and backward


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def causal_attention(q, k, v, scale, causal, use_kernel, window=None):
    """Softmax attention over grouped key/value heads: q [B, S, Hq, d],
    k and v [B, S, Hkv, d] -> [B, S, Hq, d]; query head i reads key/value
    head i // (Hq // Hkv); with *window* a query sees its *window* newest
    keys, itself among them.  With *use_kernel* forward and backward are
    the Pallas ``flash_attention`` kernels (tiles of 512, a banded grid
    under a window), else the masked softmax in ``jnp`` and its
    ``jax.vjp``."""
    return _attention(q, k, v, scale, causal, use_kernel, window)[0]


def _heads_first(*xs):
    return tuple(x.transpose(0, 2, 1, 3) for x in xs)


def _attention_scope(window, direction):
    return jax.named_scope("%s_attention_%s" % (
        "causal" if window is None else "window", direction))


def _attention(q, k, v, scale, causal, use_kernel, window, keep=None):
    """(the result, what the backward reads); *keep* is applied to the
    kernel's ``out`` and ``lse`` before anything reads them."""
    from . import pallas_kernels as pk
    with _attention_scope(window, "fwd"):
        qh, kh, vh = _heads_first(q, k, v)
        if use_kernel:
            out, lse = pk._flash_fwd_impl(qh, kh, vh, causal, scale,
                                          _ATTENTION_TILE, _ATTENTION_TILE,
                                          False, window)
            if keep is not None:
                out, lse = keep(out, lse)
            saved = (qh, kh, vh, out, lse)
        else:
            out = _masked_softmax_attention(qh, kh, vh, scale, causal,
                                            window)
            saved = (qh, kh, vh)
    return out.transpose(0, 2, 1, 3), saved


def _attention_fwd(q, k, v, scale, causal, use_kernel, window):
    """The forward rule.  On the kernel path a recomputation segment keeps
    ``out`` and ``lse`` (``ops/remat.py``): together one activation of the
    op's own size plus a float32 row statistic (134 + 2 MB a layer at
    16,384 tokens, 32 heads of 128), for which the segment's replay would
    otherwise run the whole Pallas forward again, S x S or S x window
    work, only to hand the backward kernels what was there already; with
    or without a window.  The projections that make q, k and v are
    replayed as before.  The ``jnp`` path keeps nothing: its backward is
    ``jax.vjp`` of the forward and reads only q, k, v."""
    return _attention(q, k, v, scale, causal, use_kernel, window,
                      _remat.keep)


def _attention_bwd(scale, causal, use_kernel, window, saved, do):
    from . import pallas_kernels as pk
    with _attention_scope(window, "bwd"):
        (doh,) = _heads_first(do)
        if use_kernel:
            grads = pk._flash_bwd_impl(*saved, doh, causal, scale,
                                       _ATTENTION_TILE, False, window)
        else:
            grads = jax.vjp(lambda *x: _masked_softmax_attention(
                *x, scale, causal, window), *saved)[1](doh)
        return _heads_first(*grads)


causal_attention.defvjp(_attention_fwd, _attention_bwd)


@register("_contrib_CausalAttention", aliases=["CausalAttention"])
def _causal_attention(query, key, value, scale=None, causal=True,
                      window=None, **kw):
    """Softmax attention with grouped key/value heads: query
    [B, S, Hq, d], key/value [B, S, Hkv, d] -> [B, S, Hq, d]; scores are
    scaled by ``scale`` (1/sqrt(d) if None) and masked to s <= t when
    ``causal``, and to t - window < s <= t with a ``window`` (the token
    itself among its ``window`` keys; causal only).  The forward is the
    Pallas kernel on a TPU and the same mathematics in ``jnp``
    elsewhere."""
    d = query.shape[-1]
    scale = 1.0 / d ** 0.5 if scale is None else float(scale)
    causal = bool(causal)
    if window is None:
        _tel.bump("causal_attention_traced")
    else:
        window = int(window)
        if window < 1 or not causal:
            raise ValueError("_contrib_CausalAttention: window %d needs "
                             "causal attention and at least one key"
                             % window)
        _tel.bump("window_attention_traced")
    return causal_attention(query, key, value, scale, causal,
                            jax.default_backend() == "tpu", window)


def causal_taps(x, weight, bias=None):
    """Causal depthwise convolution along the sequence in float32: x
    [B, S, C], weight [C, K], bias [C] or None -> float32 [B, S, C],
    c_t = sum_j weight[:, j] x_{t-K+1+j} (+ bias), zeros before the
    sequence."""
    taps, s = weight.shape[1], x.shape[1]
    xp = jnp.pad(x.astype(jnp.float32), [(0, 0), (taps - 1, 0), (0, 0)])
    w = weight.astype(jnp.float32)
    c = sum(w[:, j] * xp[:, j:j + s] for j in range(taps))
    return c if bias is None else c + bias.astype(jnp.float32)


def _kernel_backend():
    return jax.default_backend() == "tpu"


def sequence_conv(data, weight, bias=None, silu=False, gated=False,
                  begin=0):
    """What both convolution ops compute, in ``data``'s dtype: the taps
    over channels *begin* .. *begin* + C of ``data`` (gated: over Bg * u,
    ``data`` [B, S, 3C] holding Bg, Cg, u in that order), the bias, SiLU
    if *silu*, one rounding, times Cg if *gated*.  On a TPU, where the
    kernels take the shapes (``causal_conv_kernel_fits``: channels in
    whole lane tiles, the sequence in whole tiles of 512, at most 8 taps),
    one Pallas pass forward and one backward (``causal_conv_fwd`` /
    ``causal_conv_bwd``, counter ``causal_conv_kernel_traced``) that read
    the channels where they lie if *begin* is at a whole channel tile and
    a sliced copy of them if not; else the ``jnp`` form below, which is
    the definition."""
    from .pallas_kernels import (causal_conv_kernel_fits,
                                 causal_conv_reads_in_place)
    channels = weight.shape[0]
    kernels = _kernel_backend() and causal_conv_kernel_fits(
        data.shape[1], channels, weight.shape[1])
    if not gated and data.shape[-1] != channels and not (
            kernels and causal_conv_reads_in_place(channels, begin)):
        data = jax.lax.slice_in_dim(data, begin, begin + channels, axis=-1)
        begin = 0
    if kernels:
        _tel.bump("causal_conv_kernel_traced")
        if bias is None:        # adding zeros changes nothing
            bias = jnp.zeros(weight.shape[:1], weight.dtype)
        return _conv_kernels(data, weight, bias, silu, gated, begin)
    if gated:
        bg, cg, u = jnp.split(data, 3, axis=-1)
    c = causal_taps(bg * u if gated else data, weight, bias)
    if silu:
        c = c * jax.nn.sigmoid(c)
    return cg * c.astype(data.dtype) if gated else c.astype(data.dtype)


def _conv_scope(gated):
    return jax.named_scope("short_conv" if gated else "causal_conv")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _conv_kernels(data, weight, bias, silu, gated, begin):
    """``sequence_conv`` as the two Pallas kernels.  Nothing is kept for
    the backward but the op's inputs: it remakes the pre-activation from
    them in VMEM."""
    from .pallas_kernels import _causal_conv_fwd_impl
    return _causal_conv_fwd_impl(data, weight, bias, silu, gated, begin,
                                 False)


def _conv_kernels_fwd(data, weight, bias, silu, gated, begin):
    return _conv_kernels(data, weight, bias, silu, gated, begin), \
        (data, weight, bias)


def _conv_kernels_bwd(silu, gated, begin, saved, dy):
    from .pallas_kernels import _causal_conv_bwd_impl
    with _conv_scope(gated):
        ddata, dw, dbias = _causal_conv_bwd_impl(*saved, dy, silu, gated,
                                                 begin, False)
        after = saved[0].shape[-1] - begin - ddata.shape[-1]
        if begin or after:      # the channels the op did not read
            ddata = jnp.pad(ddata, [(0, 0), (0, 0), (begin, after)])
        return ddata, dw, dbias


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


def short_conv(data, weight):
    """The gated causal short convolution of one layer: data [B, S, 3C]
    holds (Bg, Cg, u) in that order, weight [C, K] is depthwise and has no
    bias: c_t = sum_j weight[:, j] (Bg u)_{t-K+1+j}, zeros before the
    sequence; returns Cg * c, [B, S, C].  The taps are ``causal_taps``,
    which ``_contrib_CausalConv1D`` shares through ``sequence_conv``."""
    with _conv_scope(True):
        return sequence_conv(data, weight, gated=True)


@register("_contrib_ShortConv", aliases=["ShortConv"])
def _short_conv(data, weight, **kw):
    """Gated causal depthwise convolution along the sequence: data
    [B, S, 3C] (the input projection's Bg, Cg, u), weight [C, K] ->
    [B, S, C] = Cg * conv(Bg * u)."""
    _tel.bump("short_conv_traced")
    return short_conv(data, weight)


@register("_contrib_CausalConv1D", aliases=["CausalConv1D"])
def _causal_conv1d(data, weight, *maybe_bias, act_type="silu",
                   no_bias=False, begin=0, end=None, **kw):
    """Causal depthwise convolution along the sequence with an optional
    bias and activation: data [B, S, W], weight [C, K], bias [C] ->
    [B, S, C] = act(conv(data[..., begin:end]) + bias), float32 inside;
    ``act_type`` is ``silu`` or None.  ``begin`` and ``end`` (0 and W by
    default) name the channels of a wider tensor that are the op's input,
    so that a projection's output need not be sliced first."""
    if act_type not in ("silu", None):
        raise ValueError("_contrib_CausalConv1D: act_type %r is not "
                         "implemented (silu and None are)" % (act_type,))
    begin = int(begin)
    end = data.shape[-1] if end is None else int(end)
    if not 0 <= begin < end <= data.shape[-1] or \
            end - begin != weight.shape[0]:
        raise ValueError("_contrib_CausalConv1D: channels %d:%d of %d do "
                         "not match a weight of %d channels"
                         % (begin, end, data.shape[-1], weight.shape[0]))
    _tel.bump("causal_conv_traced")
    with _conv_scope(False):
        return sequence_conv(
            data, weight, None if no_bias or not maybe_bias
            else maybe_bias[0], act_type == "silu", begin=begin)


@register("_contrib_StateSpaceScan", aliases=["StateSpaceScan"])
def _state_space_scan(data, dt, a_log, b, c, d, chunk=256, **kw):
    """Mamba-2's selective state-space scan: data [B, S, H, P], dt
    [B, S, H] (after the softplus; float32 inside), a_log and d [H], b and
    c [B, S, G, N] -> [B, S, H, P]; head h reads group h // (H // G).  For
    each head, with a state S [N, P] that starts at zero: S_t =
    exp(-exp(a_log) dt_t) S_{t-1} + dt_t b_t x_t^T, y_t = c_t S_t + d x_t,
    in the chunked dual form; the forward and the hand-derived backward
    are Pallas kernels on a TPU and the same algorithm in ``jnp``
    elsewhere."""
    from .pallas_kernels import state_space_kernel_fits, state_space_scan
    chunk = int(chunk)
    _tel.bump("state_space_traced")
    _tel.bump("state_space_chunks", -(-data.shape[1] // chunk))
    kernel = jax.default_backend() == "tpu" and \
        state_space_kernel_fits(data, b, chunk)
    return state_space_scan(data, dt.astype(jnp.float32), a_log, b, c, d,
                            chunk, kernel)


def _head_blocks(data, label, block):
    """Tokens flattened and padded to whole blocks: hidden states
    [n, block, H], labels [n, block] (-1 on the padding)."""
    h = data.reshape(-1, data.shape[-1])
    y = label.reshape(-1).astype(jnp.int32)
    n = -(-h.shape[0] // block)
    pad = n * block - h.shape[0]
    if pad:
        h = jnp.pad(h, [(0, pad), (0, 0)])
        y = jnp.pad(y, [(0, pad)], constant_values=-1)
    return h.reshape(n, block, -1), y.reshape(n, block), h.shape[0] - pad


def _block_logits(h, weight):
    return jax.lax.dot_general(h, weight, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _block_nll(logits, y):
    """Sum of -log softmax(logits)[y] over a block's rows with y >= 0."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, jnp.maximum(y, 0)[:, None],
                                 axis=-1)[:, 0]
    return jnp.sum(jnp.where(y >= 0, lse - picked, 0.0))


def _blocked_ce_value(data, weight, label, block):
    hs, ys, count = _head_blocks(data, label, block)

    def body(total, xs):
        h, y = xs
        return total + _block_nll(_block_logits(h, weight), y), None

    with jax.named_scope("lm_head_loss"):
        total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ys))
    return (total / count).reshape(1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def blocked_softmax_ce(data, weight, label, block):
    """Mean next-token negative log-likelihood of softmax(data weight^T)
    at ``label``, one block of tokens at a time: no [tokens, classes]
    tensor is ever whole.

    Called without differentiation (``is_train=False``, ``score``,
    ``predict``) it is one scan with one product a block.  Under
    differentiation the forward rule does all of the work: the loss is the
    mean over tokens, so d loss / d logits = (softmax - onehot) / count
    depends on nothing after the op, and one scan forms the loss, ``dh`` and
    ``dW`` from one logits block (three products a block); the backward
    rule scales them by the incoming cotangent.  A ``forward(is_train=True)``
    that no ``backward()`` follows therefore pays for gradients it never
    reads: ask for the value alone with ``is_train=False``.  Counter
    ``lm_head_fused_traced``: traces of the forward rule."""
    return _blocked_ce_value(data, weight, label, block)


def _blocked_ce_fwd(data, weight, label, block):
    _tel.bump("lm_head_fused_traced")
    hs, ys, count = _head_blocks(data, label, block)
    scale = jnp.ones((), jnp.float32) / count

    def body(carry, xs):
        total, dw = carry
        h, y = xs
        logits = _block_logits(h, weight)
        total = total + _block_nll(logits, y)
        p = jax.nn.softmax(logits, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) == y[:, None]
        dlogits = ((p - hit) * jnp.where(y >= 0, scale, 0.0)[:, None]) \
            .astype(h.dtype)
        dh = jnp.dot(dlogits, weight, preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(
            dlogits, h, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (total, dw), dh.astype(h.dtype)

    with jax.named_scope("lm_head_loss"):
        (total, dw), dh = jax.lax.scan(
            body, (jnp.zeros((), jnp.float32),
                   jnp.zeros(weight.shape, jnp.float32)), (hs, ys))
    dh = dh.reshape(-1, dh.shape[-1])[:count].reshape(data.shape)
    return (total / count).reshape(1), (dh, dw.astype(weight.dtype), label)


def _blocked_ce_bwd(block, res, g):
    dh, dw, label = res
    g = g.reshape(()).astype(jnp.float32)
    return (dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype), \
        jnp.zeros_like(label)


blocked_softmax_ce.defvjp(_blocked_ce_fwd, _blocked_ce_bwd)


@register("_contrib_BlockedSoftmaxCE", aliases=["BlockedSoftmaxCE"],
          nondiff_inputs=(2,))
def _blocked_softmax_ce(data, weight, label, num_hidden=None, block=2048,
                        **kw):
    """Language-model head and loss in one op: hidden states [.., H],
    head weight [classes, H], integer labels [..] (as the label dtype the
    Module feeds) -> the mean negative log-likelihood, shape (1,), float32.
    Logits, log-softmax and their gradients are computed ``block`` tokens
    at a time."""
    return blocked_softmax_ce(data, weight, label, int(block))
