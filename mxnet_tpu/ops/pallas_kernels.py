"""Pallas TPU kernels for the hot ops.

Reference analogue: the RTC/custom-kernel surface (``src/common/rtc.cc``,
NVRTC runtime CUDA compilation; SURVEY §2.1 "RTC") — on TPU, user-authored
kernels are Pallas.  This module holds the framework's built-in kernels:

- ``flash_attention``: tiled online-softmax attention.  Grid is
  (batch·heads, q blocks, k blocks); the k dimension is the innermost
  (sequential) grid axis, so each program sees ONE [block_k, D] K/V tile in
  VMEM while fp32 accumulators persist in scratch across k steps — true
  streaming, O(block·D) VMEM regardless of sequence length.  Causal
  programs whose whole K tile is masked skip compute via ``pl.when``.
  Differentiable via ``jax.custom_vjp``; the backward recomputes scores in
  q-row chunks (O(chunk·S) memory, not O(S²)).

The kernels compile with Mosaic (``interpret=False``, the default) and
that only works on a TPU.  ``interpret=True`` is the explicit CPU-test
mode (tests/test_pallas.py); nothing here picks it silently.

Mosaic has no 64-bit types and the package runs with ``jax_enable_x64``
on, so every constant inside a kernel body carries an explicit 32-bit
dtype: a bare Python float routed through a jitted ``jnp`` helper
(``jnp.where``) would otherwise enter the kernel as an f64 operand and
fail to lower (``Unsupported cast: float64 -> float32``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention"]

_NEG = np.float32(-1e30)
_TINY = np.float32(1e-30)
_LANES = 128  # m/l scratch is lane-replicated to satisfy TPU tiling


def _lane_cols(x, n):
    """Re-width a lane-replicated [rows, 128] value to [rows, n]; n is
    below one lane tile or a multiple of it (checked by the caller)."""
    reps, rem = divmod(n, _LANES)
    if rem:
        return x[:, :n]
    return x if reps == 1 else jnp.tile(x, (1, reps))


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 block_q, block_k, causal, sm_scale, seq_len):
    """One (bh, qi, ki) program. Scratch (acc/m/l) carries across ki —
    the innermost grid axis is sequential on TPU.  Row statistics stay
    2-D ([block_q, 128], every lane equal) end to end: Mosaic lays
    vectors out on (sublane, lane) tiles and 1-D row vectors have no
    stable layout."""
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    num_k = pl.num_programs(2)
    head_dim = q_ref.shape[-1]
    # f32 in means f32 math: Mosaic's default contraction rounds f32
    # operands to bf16 for a single MXU pass
    precision = jax.lax.Precision.HIGHEST \
        if q_ref.dtype == jnp.float32 else None

    @pl.when(ki == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    # causal: skip K tiles strictly in the future of this q block
    live = True
    if causal:
        live = (qi + 1) * block_q - 1 >= ki * block_k

    @pl.when(live)
    def _step():
        # operands stay in their storage dtype (bf16 feeds the MXU at
        # full rate); products accumulate in f32
        s = jax.lax.dot_general(q_ref[:], k_ref[:],
                                (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        s = s * np.float32(sm_scale)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = k_pos < seq_len          # mask the padded K tail
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            valid = jnp.logical_and(valid, q_pos >= k_pos)
        s = jnp.where(valid, s, _NEG)

        m_prev = m_ref[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - _lane_cols(m_new, block_k))
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=1)[:, None]
        m_ref[:] = m_new
        acc_ref[:] = acc_ref[:] * _lane_cols(corr, head_dim) + \
            jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[:],
                                (((1,), (0,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)

    @pl.when(ki == num_k - 1)
    def _finalize():
        denom = _lane_cols(jnp.maximum(l_ref[:], _TINY), head_dim)
        o_ref[:] = (acc_ref[:] / denom).astype(o_ref.dtype)


def _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    b, h, s, d = q.shape
    if d > _LANES and d % _LANES:
        raise ValueError("flash_attention: head dim %d must be <= %d or a "
                         "multiple of it" % (d, _LANES))
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    bq = min(block_q, s)
    # K tiles are whole lane tiles (K/V are padded up to them below);
    # a sequence shorter than one lane tile is a single block
    bk = min(block_k, -(-s // _LANES) * _LANES) if s >= _LANES \
        else min(block_k, s)
    if bk > _LANES and bk % _LANES:
        raise ValueError("flash_attention: block_k %d must be <= %d or a "
                         "multiple of it" % (bk, _LANES))
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    # pad K/V to a block multiple: an out-of-bounds block index CLAMPS,
    # silently shifting the tail tile — padded keys are masked by seq_len
    s_pad = ((s + bk - 1) // bk) * bk
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0)]
        kf = jnp.pad(kf, pad)
        vf = jnp.pad(vf, pad)
    kernel = functools.partial(_attn_kernel, block_q=bq, block_k=bk,
                               causal=causal, sm_scale=scale, seq_len=s)
    zero = np.int32(0)      # a bare 0 is an i64 block index under x64
    out = pl.pallas_call(
        kernel,
        grid=(b * h, pl.cdiv(s, bq), s_pad // bk),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, i, t: (bh, i, zero)),
            pl.BlockSpec((None, bk, d), lambda bh, i, t: (bh, t, zero)),
            pl.BlockSpec((None, bk, d), lambda bh, i, t: (bh, t, zero)),
        ],
        out_specs=pl.BlockSpec((None, bq, d),
                               lambda bh, i, t: (bh, i, zero)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, d), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
            pltpu.VMEM((bq, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_fwd",
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, s, d)


def _chunked_attn_grads(q, k, v, do, causal, sm_scale, chunk=512):
    """Recompute backward in q-row chunks: memory O(chunk·S) per step
    instead of materializing the full S×S score/softmax matrices."""
    b, h, s, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    c = min(chunk, s)
    n = (s + c - 1) // c
    s_pad = n * c
    f32 = jnp.float32

    def padq(x):
        if s_pad != s:
            x = jnp.pad(x, [(0, 0), (0, 0), (0, s_pad - s), (0, 0)])
        return x.astype(f32).reshape(b, h, n, c, d).transpose(2, 0, 1, 3, 4)

    qs, dos = padq(q), padq(do)
    kf = k.astype(f32)
    vf = v.astype(f32)
    k_pos = jnp.arange(s)

    def body(carry, inp):
        dk_acc, dv_acc, i = carry
        q_c, do_c = inp
        s_c = jnp.einsum("bhqd,bhkd->bhqk", q_c, kf) * scale
        q_pos = i * c + jnp.arange(c)
        valid = (q_pos[:, None] < s)
        if causal:
            valid = jnp.logical_and(valid, q_pos[:, None] >= k_pos[None, :])
        s_c = jnp.where(valid, s_c, _NEG)
        p = jax.nn.softmax(s_c, axis=-1)
        dv_acc = dv_acc + jnp.einsum("bhqk,bhqd->bhkd", p, do_c)
        dp = jnp.einsum("bhqd,bhkd->bhqk", do_c, vf)
        ds = p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))
        ds = jnp.where(valid, ds, 0.0)
        dq_c = jnp.einsum("bhqk,bhkd->bhqd", ds, kf) * scale
        dk_acc = dk_acc + jnp.einsum("bhqk,bhqd->bhkd", ds, q_c) * scale
        return (dk_acc, dv_acc, i + 1), dq_c

    zeros = jnp.zeros((b, h, s, d), f32)
    (dk, dv, _), dq_chunks = jax.lax.scan(
        body, (zeros, zeros, jnp.int32(0)), (qs, dos))
    dq = dq_chunks.transpose(1, 2, 0, 3, 4).reshape(b, h, s_pad, d)[:, :, :s]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=128,
                    block_k=128, interpret=False):
    """Tiled flash attention: q, k, v [B, H, S, D] -> [B, H, S, D].

    Pallas streaming forward (K/V tiles via the sequential grid axis,
    causal tile skipping); q-chunked recompute backward.
    ``interpret=True`` runs the kernel in the Pallas interpreter (CPU
    tests).  Shard batch/head dims with ``shard_map`` before calling —
    pallas_call is opaque to GSPMD.
    """
    return _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                           interpret)


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret):
    out = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                          interpret)
    return out, (q, k, v)


def _bwd(causal, sm_scale, block_q, block_k, interpret, res, do):
    q, k, v = res
    return _chunked_attn_grads(q, k, v, do, causal, sm_scale)


flash_attention.defvjp(_fwd, _bwd)
