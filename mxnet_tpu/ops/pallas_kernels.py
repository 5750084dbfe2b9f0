"""Pallas TPU kernels for the hot ops.

Reference analogue: the RTC/custom-kernel surface (``src/common/rtc.cc``,
NVRTC runtime CUDA compilation; SURVEY §2.1 "RTC") — on TPU, user-authored
kernels are Pallas.  This module holds the framework's built-in kernels:

- ``flash_attention``: tiled online-softmax attention.  Grid is
  (batch·heads, q blocks, k blocks); the k dimension is the innermost
  (sequential) grid axis, so each program sees ONE [block_k, D] K/V tile in
  VMEM while fp32 accumulators persist in scratch across k steps — true
  streaming, O(block·D) VMEM regardless of sequence length.  Causal
  programs whose whole K tile is masked skip compute via ``pl.when``
  (the tile is still stepped over and fetched); under a sliding
  ``window`` the innermost axis is only as long as the band of tiles a
  query tile can see, offset by the block map, so tiles outside the band
  are never visited.
  Grouped key/value heads are an index in the block map, never a repeated
  tensor.  Differentiable via ``jax.custom_vjp``: the forward also writes
  the softmax's log-sum-exp, and two Pallas kernels (dq; dk and dv) recompute
  the probabilities tile by tile from it, O(block²) memory.

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

from .. import telemetry as _tel
from . import remat as _remat

__all__ = ["flash_attention", "power_retention", "state_space_scan"]

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


def _i32(op, x, n):
    """``op(x, n)`` on a traced 32-bit scalar and a Python integer (block
    maps, kernel bodies; under x64 ``jnp``'s own would bring 64-bit
    constants into Mosaic)."""
    return op(x, np.int32(n))


def _band_of_queries(qi, *, block_q, block_k, window, seq_len, **_):
    """(first, last) key tile that query tile *qi* of a windowed causal
    layer sees: keys q_pos - window < k_pos <= q_pos."""
    first = _i32(jax.lax.div, _i32(
        jax.lax.max, qi * block_q - (window - 1), 0), block_k)
    last = _i32(jax.lax.min, _i32(jax.lax.div, (qi + 1) * block_q - 1,
                                  block_k), -(-seq_len // block_k) - 1)
    return first, last


def _band_of_keys(ki, *, block_q, block_k, window, seq_len, **_):
    """(first, last) query tile that sees key tile *ki* under a window."""
    first = _i32(jax.lax.div, ki * block_k, block_q)
    last = _i32(jax.lax.min, _i32(
        jax.lax.div, (ki + 1) * block_k + (window - 2), block_q),
        -(-seq_len // block_q) - 1)
    return first, last


def _band_len(block_outer, block_inner, window, n_inner):
    """Static length of a banded grid axis: the most inner tiles an outer
    tile sees.  Its positions and the window - 1 behind (or ahead of)
    them: ceil((window - 1) / tile) + 1 tiles when both tiles are one
    size, and for unlike tiles one more than they span at most."""
    if block_outer == block_inner:
        tiles = -(-(window - 1) // block_inner) + 1
    else:
        tiles = -(-(block_outer + window - 2) // block_inner) + 1
    return min(tiles, n_inner)


def _band_tile(band, outer, step, **tile):
    """(inner tile at grid step *step*, whether it is inside the band):
    the band starts at the outer tile's first visible tile; steps past
    its last one name that last tile again (the block map repeats the
    block, so nothing is fetched) and are masked off."""
    first, last = band(outer, **tile)
    return jax.lax.min(first + step, last), first + step <= last


def _key_tile(qi, step, **tile):
    """(key tile, whether any of it may be visible) of grid step *step*
    of query tile *qi*: without a window every key tile is stepped over
    and those in a causal query tile's future are skipped; with one only
    the band is visited."""
    if tile.get("window") is not None:
        return _band_tile(_band_of_queries, qi, step, **tile)
    live = True
    if tile["causal"]:
        # causal: skip K tiles strictly in the future of this q block
        live = (qi + 1) * tile["block_q"] - 1 >= step * tile["block_k"]
    return step, live


def _query_tile(ki, step, **tile):
    """The same for the query tiles of key tile *ki* (dk and dv)."""
    if tile.get("window") is not None:
        return _band_tile(_band_of_keys, ki, step, **tile)
    live = True
    if tile["causal"]:
        live = (step + 1) * tile["block_q"] - 1 >= ki * tile["block_k"]
    return step, live


def _visible(qi, ki, *, block_q, block_k, causal, seq_len, window=None):
    """[block_q, block_k] mask of tile (qi, ki): the key is no padding,
    not in the query's future (causal) and at most window - 1 behind."""
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    valid = k_pos < seq_len          # mask the padded K tail
    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        valid = jnp.logical_and(valid, q_pos >= k_pos)
        if window is not None:
            valid = jnp.logical_and(valid, q_pos - k_pos < window)
    return valid


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                 *, sm_scale, **tile):
    """One (bh, qi, step) program. Scratch (acc/m/l) carries across the
    innermost grid axis, which is sequential on TPU: every key tile
    without a window, the band's tiles with one.  Row statistics stay
    2-D ([block_q, 128], every lane equal) end to end: Mosaic lays
    vectors out on (sublane, lane) tiles and 1-D row vectors have no
    stable layout."""
    block_q, block_k = tile["block_q"], tile["block_k"]
    qi = pl.program_id(1)
    step = pl.program_id(2)
    num_k = pl.num_programs(2)
    head_dim = q_ref.shape[-1]
    # f32 in means f32 math: Mosaic's default contraction rounds f32
    # operands to bf16 for a single MXU pass
    precision = jax.lax.Precision.HIGHEST \
        if q_ref.dtype == jnp.float32 else None

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG)
        l_ref[:] = jnp.zeros_like(l_ref)

    ki, live = _key_tile(qi, step, **tile)

    @pl.when(live)
    def _step():
        # operands stay in their storage dtype (bf16 feeds the MXU at
        # full rate); products accumulate in f32
        s = jax.lax.dot_general(q_ref[:], k_ref[:],
                                (((1,), (1,)), ((), ())),
                                precision=precision,
                                preferred_element_type=jnp.float32)
        s = s * np.float32(sm_scale)
        s = jnp.where(_visible(qi, ki, **tile), s, _NEG)

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

    @pl.when(step == num_k - 1)
    def _finalize():
        total = jnp.maximum(l_ref[:], _TINY)
        o_ref[:] = (acc_ref[:] / _lane_cols(total, head_dim)) \
            .astype(o_ref.dtype)
        # log of the softmax's denominator, for the backward kernels
        lse_ref[:] = m_ref[:] + jnp.log(total)


def _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k, interpret,
                    window=None):
    """(output [B, H, S, D], log-sum-exp of the scaled scores [B, H, S]).

    Without a *window* the grid steps over every (query tile, key tile)
    pair: a causal query tile's future key tiles are fetched and skipped
    (``pl.when``).  With one (causal only: keys q_pos - window < k_pos <=
    q_pos) the innermost axis is as long as the band, ceil((window - 1) /
    tile) + 1 key tiles for square tiles, and the block map offsets it by
    the query tile's first visible key tile: tiles outside the band are
    never visited, so the kernel's time follows S x window.  Near the
    sequence's start the band is shorter than the axis; the surplus steps
    name the band's last tile again, fetch nothing and compute nothing."""
    b, h, s, d = q.shape
    if window is not None and not causal:
        raise ValueError("flash_attention: a window of %s keys needs "
                         "causal=True" % (window,))
    hkv = k.shape[1]
    if h % hkv or v.shape[1] != hkv:
        raise ValueError("flash_attention: %d query heads over %d/%d "
                         "key/value heads" % (h, hkv, v.shape[1]))
    group = np.int32(h // hkv)
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
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)
    # pad K/V to a block multiple: an out-of-bounds block index CLAMPS,
    # silently shifting the tail tile — padded keys are masked by seq_len
    s_pad = ((s + bk - 1) // bk) * bk
    if s_pad != s:
        pad = [(0, 0), (0, s_pad - s), (0, 0)]
        kf = jnp.pad(kf, pad)
        vf = jnp.pad(vf, pad)
    tile = dict(block_q=bq, block_k=bk, causal=causal, seq_len=s)
    n_q, n_k = pl.cdiv(s, bq), s_pad // bk
    if window is not None:
        tile["window"] = int(window)
        n_k = _band_len(bq, bk, tile["window"], n_k)
    kernel = functools.partial(_attn_kernel, sm_scale=scale, **tile)
    zero = np.int32(0)      # a bare 0 is an i64 block index under x64

    # query head bh reads key/value head bh // group (heads are the inner
    # axis of both): the repeat is an index, never a tensor
    def of_key(bh, i, t):
        return (bh // group, _key_tile(i, t, **tile)[0], zero)

    out, lse = pl.pallas_call(
        kernel,
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, i, t: (bh, i, zero)),
            pl.BlockSpec((None, bk, d), of_key),
            pl.BlockSpec((None, bk, d), of_key),
        ],
        out_specs=[
            pl.BlockSpec((None, bq, d), lambda bh, i, t: (bh, i, zero)),
            pl.BlockSpec((None, bq, _LANES), lambda bh, i, t: (bh, i, zero)),
        ],
        out_shape=[jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                   jax.ShapeDtypeStruct((b * h, s, _LANES), jnp.float32)],
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
    return out.reshape(b, h, s, d), lse[..., 0].reshape(b, h, s)


def _attn_probs(s, lse, qi, ki, **tile):
    """exp(s - lse) on a [block_q, block_k] tile of scaled scores, zero
    where the key is padding, in the query's future (causal) or behind
    its window."""
    return jnp.where(_visible(qi, ki, **tile),
                     jnp.exp(s - _lane_cols(lse, tile["block_k"])),
                     np.float32(0.0))


def _attn_tile_grads(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
                     *, sm_scale, **tile):
    """(p, ds) of one tile: the probabilities recomputed from the saved
    log-sum-exp, and the scaled scores' cotangent p * (do v^T - delta),
    both in the operands' dtype for the products that follow."""
    precision = jax.lax.Precision.HIGHEST \
        if q_ref.dtype == jnp.float32 else None
    s = jax.lax.dot_general(q_ref[:], k_ref[:], (((1,), (1,)), ((), ())),
                            precision=precision,
                            preferred_element_type=jnp.float32)
    p = _attn_probs(s * np.float32(sm_scale), lse_ref[:], qi, ki, **tile)
    dp = jax.lax.dot_general(do_ref[:], v_ref[:], (((1,), (1,)), ((), ())),
                             precision=precision,
                             preferred_element_type=jnp.float32)
    ds = p * (dp - _lane_cols(delta_ref[:], tile["block_k"]))
    return p.astype(q_ref.dtype), ds.astype(q_ref.dtype), precision


def _attn_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                    acc_ref, *, sm_scale, **tile):
    """One (bh, qi, step) program of dq = scale * ds k, the key tiles of
    ``_key_tile`` in sequence."""
    qi, step = pl.program_id(1), pl.program_id(2)

    @pl.when(step == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ki, live = _key_tile(qi, step, **tile)

    @pl.when(live)
    def _step():
        _, ds, precision = _attn_tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            sm_scale=sm_scale, **tile)
        acc_ref[:] = acc_ref[:] + jax.lax.dot_general(
            ds, k_ref[:], (((1,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

    @pl.when(step == pl.num_programs(2) - 1)
    def _finalize():
        dq_ref[:] = (acc_ref[:] * np.float32(sm_scale)).astype(dq_ref.dtype)


def _attn_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                     dv_ref, dk_acc, dv_acc, *, sm_scale, **tile):
    """One (key/value head, ki, query head of its group, step) program of
    dv = p^T do and dk = scale * ds^T q; the last two axes sequential, so
    a key/value head's gradient gathers over the query heads that read
    it and over the query tiles of ``_query_tile``."""
    ki, gi, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(jnp.logical_and(gi == 0, step == 0))
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    qi, live = _query_tile(ki, step, **tile)

    @pl.when(live)
    def _step():
        p, ds, precision = _attn_tile_grads(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, qi, ki,
            sm_scale=sm_scale, **tile)
        dv_acc[:] = dv_acc[:] + jax.lax.dot_general(
            p, do_ref[:], (((0,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)
        dk_acc[:] = dk_acc[:] + jax.lax.dot_general(
            ds, q_ref[:], (((0,), (0,)), ((), ())), precision=precision,
            preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(gi == pl.num_programs(2) - 1,
                             step == pl.num_programs(3) - 1))
    def _finalize():
        dk_ref[:] = (dk_acc[:] * np.float32(sm_scale)).astype(dk_ref.dtype)
        dv_ref[:] = dv_acc[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, out, lse, do, causal, sm_scale, block,
                    interpret, window=None):
    """Gradients of ``_flash_fwd_impl`` by two Pallas kernels over square
    tiles of *block*: the probabilities are recomputed from the forward's
    log-sum-exp, and a key/value head's gradient gathers over its group
    of query heads inside the kernel.  Without a *window* both grids step
    over every tile pair and skip (``pl.when``) the causal tiles in a
    query's future; with one the innermost axis of each is the band —
    the key tiles a query tile sees in ``dq``, the query tiles that see a
    key tile in ``dk``/``dv`` — and tiles outside it are never visited
    (``_flash_fwd_impl``).  Everything is padded with zeros to whole
    tiles: a padded query row has do = 0 and delta = 0 and adds nothing."""
    b, h, s, d = q.shape
    hkv = k.shape[1]
    group = np.int32(h // hkv)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    blk = min(block, -(-s // _LANES) * _LANES)
    n = -(-s // blk)
    s_pad = n * blk

    def rows(x, heads):
        x = x.reshape((b * heads, s) + x.shape[3:])
        if s_pad != s:
            x = jnp.pad(x, [(0, 0), (0, s_pad - s)] + [(0, 0)] * (x.ndim - 2))
        return x

    def lanes(x):           # [b, h, s] -> [b*h, s_pad, 128], every lane equal
        return jnp.broadcast_to(rows(x, h)[..., None],
                                (b * h, s_pad, _LANES))

    do = do.astype(q.dtype)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)
    qf, dof, kf, vf = rows(q, h), rows(do, h), rows(k, hkv), rows(v, hkv)
    lsef, deltaf = lanes(lse), lanes(delta)
    tile = dict(block_q=blk, block_k=blk, causal=causal, seq_len=s)
    n_keys = n_queries = n
    if window is not None:
        tile["window"] = int(window)
        n_keys = n_queries = _band_len(blk, blk, tile["window"], n)
    zero = np.int32(0)

    def of_row(bh, i, t):
        return (bh, i, zero)

    def of_band_key(bh, i, t):
        return (bh // group, _key_tile(i, t, **tile)[0], zero)

    dq = pl.pallas_call(
        functools.partial(_attn_dq_kernel, sm_scale=scale, **tile),
        grid=(b * h, n, n_keys),
        in_specs=[
            pl.BlockSpec((None, blk, d), of_row),
            pl.BlockSpec((None, blk, d), of_band_key),
            pl.BlockSpec((None, blk, d), of_band_key),
            pl.BlockSpec((None, blk, d), of_row),
            pl.BlockSpec((None, blk, _LANES), of_row),
            pl.BlockSpec((None, blk, _LANES), of_row),
        ],
        out_specs=pl.BlockSpec((None, blk, d), of_row),
        out_shape=jax.ShapeDtypeStruct((b * h, s_pad, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention_dq",
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)

    def of_query(kv, t, g, i):
        return (kv * group + g, _query_tile(t, i, **tile)[0], zero)

    def of_key(kv, t, g, i):
        return (kv, t, zero)

    dk, dv = pl.pallas_call(
        functools.partial(_attn_dkv_kernel, sm_scale=scale, **tile),
        grid=(b * hkv, n, int(group), n_queries),
        in_specs=[
            pl.BlockSpec((None, blk, d), of_query),
            pl.BlockSpec((None, blk, d), of_key),
            pl.BlockSpec((None, blk, d), of_key),
            pl.BlockSpec((None, blk, d), of_query),
            pl.BlockSpec((None, blk, _LANES), of_query),
            pl.BlockSpec((None, blk, _LANES), of_query),
        ],
        out_specs=[pl.BlockSpec((None, blk, d), of_key),
                   pl.BlockSpec((None, blk, d), of_key)],
        out_shape=[jax.ShapeDtypeStruct((b * hkv, s_pad, d), k.dtype),
                   jax.ShapeDtypeStruct((b * hkv, s_pad, d), v.dtype)],
        scratch_shapes=[pltpu.VMEM((blk, d), jnp.float32),
                        pltpu.VMEM((blk, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        name="flash_attention_dkv",
        interpret=interpret,
    )(qf, kf, vf, dof, lsef, deltaf)
    return (dq[:, :s].reshape(q.shape), dk[:, :s].reshape(k.shape),
            dv[:, :s].reshape(v.shape))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=False, sm_scale=None, block_q=128,
                    block_k=128, interpret=False, window=None):
    """Tiled flash attention: q [B, H, S, D], k and v [B, Hkv, S, D] ->
    [B, H, S, D]; query head i reads key/value head i // (H // Hkv).
    With *window* (causal only) a query sees the *window* newest keys,
    itself among them: q_pos - window < k_pos <= q_pos.

    Pallas streaming forward (K/V tiles via the sequential grid axis,
    causal tile skipping, a banded grid under a window) and Pallas
    backward (``flash_attention_dq``, ``flash_attention_dkv``) from the
    forward's saved log-sum-exp.  ``interpret=True`` runs the kernel in
    the Pallas interpreter (CPU tests).  Shard batch/head dims with
    ``shard_map`` before calling — pallas_call is opaque to GSPMD.
    """
    return _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                           interpret, window)[0]


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, interpret, window):
    out, lse = _flash_fwd_impl(q, k, v, causal, sm_scale, block_q, block_k,
                               interpret, window)
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, block_q, block_k, interpret, window, res, do):
    return _flash_bwd_impl(*res, do, causal, sm_scale, max(block_q, block_k),
                           interpret, window)


flash_attention.defvjp(_fwd, _bwd)


# --- power retention --------------------------------------------------------
# o_t = sum_{s<=t} w_ts v_s / (sum_{s<=t} w_ts + eps),
# w_ts = (scale q_t.k_s)^2 exp(c_t - c_s), c the running sum of the log-gate.
# The chunked state form carries, per key/value head, S = sum_s decay
# phi(k_s) v_s^T and z = sum_s decay phi(k_s) with phi(u) = vec(u u^T), so
# that phi(q).phi(k) = (q.k)^2.  Both are held in the redundant d x d form
# (twice the d(d+1)/2 distinct entries): S as [i, v, j], z as the matrix
# Z[i, j] = sum_s decay k_si k_sj, which turns phi(q).z into rowsum((q Z) q).

def _f32_acc(product, cd):
    """*product* (``jnp.einsum``, ``lax.dot_general``) over operands of
    dtype *cd* with float32 accumulation."""
    prec = jax.lax.Precision.HIGHEST if cd == jnp.float32 else None
    return functools.partial(product, precision=prec,
                             preferred_element_type=jnp.float32)


def _retention_chunk(S, Z, q, k, v, a, *, scale, eps):
    """One chunk of one key/value head in ``jnp``: state at the chunk's
    start -> (state at its end, outputs).  S [d, dv, d] and Z [d, d] are
    float32; q [G, C, d], k [C, d], v [C, dv] keep their dtype as matmul
    operands with float32 accumulation; a [C] is the float32 log-gate."""
    f32, cd = jnp.float32, q.dtype
    ein = _f32_acc(jnp.einsum, cd)
    c = jnp.cumsum(a.astype(f32))
    t = jnp.arange(c.shape[0])
    diff = jnp.where(t[:, None] >= t[None, :], c[:, None] - c[None, :],
                     -jnp.inf)
    s = ein("gtd,sd->gts", q, k) * scale
    w = s * s * jnp.exp(diff)
    num = ein("gts,sv->gtv", w.astype(cd), v)
    den = jnp.sum(w, axis=-1)
    # what the state at the chunk's start adds, decayed to each token
    reach = (scale * scale) * jnp.exp(c)
    pq = q[..., :, None] * q[..., None, :]
    num = num + ein("gtij,ivj->gtv", pq, S.astype(cd)) * reach[:, None]
    den = den + jnp.sum(ein("gti,ij->gtj", q, Z.astype(cd)) * q.astype(f32),
                        axis=-1) * reach
    o = (num / (den + eps)[..., None]).astype(cd)
    return _retention_advance(S, Z, k, v, c) + (o,)


def _retention_advance(S, Z, k, v, c):
    """The state once a chunk: at the chunk's start -> at its end, from
    the keys, the values and the running log-gate c [C] alone."""
    f32, cd = jnp.float32, k.dtype
    ein = _f32_acc(jnp.einsum, cd)
    to_end = jnp.exp(c[-1] - c)
    pk = k[:, :, None] * k[:, None, :]
    vd = (v.astype(f32) * to_end[:, None]).astype(cd)
    kd = (k.astype(f32) * to_end[:, None]).astype(cd)
    S_new = jnp.exp(c[-1]) * S + ein("sij,sv->ivj", pk, vd)
    Z_new = jnp.exp(c[-1]) * Z + ein("si,sj->ij", kd, k)
    return S_new, Z_new


def _retention_heads(q, k, v, a, chunk):
    """[B, S, H*, d] operands -> per key/value head, chunked:
    q [B*Hkv, n, G, C, d], k/v [B*Hkv, n, C, d], a [B*Hkv, n, C]; the tail
    is padded with zero keys, values and log-gates, which come after every
    real token and so reach none."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    n = -(-s // chunk)
    pad = n * chunk - s

    def lay(x):             # [B, S, Hkv, .., last] -> [B*Hkv, n, .., C, last]
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((b, n, chunk) + x.shape[2:])
        x = jnp.moveaxis(x, 2, -2)          # [B, n, Hkv, .., C, last]
        x = jnp.moveaxis(x, 1, 2)
        return x.reshape((b * hkv,) + x.shape[2:])

    return (lay(q.reshape(b, s, hkv, hq // hkv, d)), lay(k), lay(v),
            lay(a[..., None])[..., 0])


def _retention_unlay(o, b, s):
    """[B*Hkv, n, G, C, dv] -> [B, S, Hq, dv]."""
    bh, n, g, c, dv = o.shape
    o = o.reshape(b, bh // b, n, g, c, dv)
    o = jnp.transpose(o, (0, 2, 4, 1, 3, 5))
    return o.reshape(b, n * c, (bh // b) * g, dv)[:, :s]


def _retention_zero_state(kh, vh):
    bh, d, dv = kh.shape[0], kh.shape[-1], vh.shape[-1]
    return (jnp.zeros((bh, d, dv, d), jnp.float32),
            jnp.zeros((bh, d, d), jnp.float32))


def _retention_scan(qh, kh, vh, ah, scale, eps):
    """The chunked state form in ``jnp``: scan over the chunks of every
    head at once.  Returns the outputs."""
    step = jax.vmap(functools.partial(_retention_chunk, scale=scale,
                                      eps=eps))

    def body(carry, xs):
        S1, Z1, o = step(*carry, *xs)
        return (S1, Z1), o

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (qh, kh, vh, ah))
    o = jax.lax.scan(body, _retention_zero_state(kh, vh), xs)[1]
    return jnp.moveaxis(o, 0, 1)


def _retention_states_scan(kh, vh, ah):
    """The state at each chunk's start in ``jnp``, S0 [bh, n, d, dv, d]
    and Z0 [bh, n, d, d] in the operands' dtype (the backward's matmul
    operands): ``_retention_scan``'s advance and nothing else."""
    step = jax.vmap(_retention_advance)

    def body(carry, xs):
        k, v, a = xs
        c = jnp.cumsum(a.astype(jnp.float32), axis=-1)
        return step(*carry, k, v, c), tuple(x.astype(kh.dtype)
                                            for x in carry)

    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (kh, vh, ah))
    states = jax.lax.scan(body, _retention_zero_state(kh, vh), xs)[1]
    return tuple(jnp.moveaxis(x, 0, 1) for x in states)


_NN = (((1,), (0,)), ((), ()))
_NT = (((1,), (1,)), ((), ()))


# What the forward kernel and the states kernel share: the float32 state
# (st [i, v, j] and z scratch) carried across the chunk axis, the
# innermost, sequential one, and advanced by the same operations in the
# same order, so that both hold the same bits at every chunk's start.

def _state_enter(st_ref, z_ref, kf_ref, kT_ref, crow_ref):
    """Zero state at a head's first chunk, the keys in float32; -> the
    running log-gate [1, C], the decay from each token to the chunk's
    end [1, C] and over the whole chunk [1, 1]."""
    @pl.when(pl.program_id(1) == 0)
    def _init():
        st_ref[:] = jnp.zeros_like(st_ref)
        z_ref[:] = jnp.zeros_like(z_ref)

    c = kT_ref.shape[1]
    crow = crow_ref[:]
    c_end = crow[:, c - 1:c]
    kf_ref[:] = kT_ref[:].astype(jnp.float32)
    return crow, jnp.exp(c_end - crow), jnp.exp(c_end)


def _state_advance_z(z_ref, kf_ref, kT_ref, vT_ref, to_end, grow):
    """z to the chunk's end; -> the values decayed to it, [dv, s]."""
    f32, cd = jnp.float32, kT_ref.dtype
    vd = (vT_ref[:].astype(f32) * to_end).astype(cd)
    kd = (kf_ref[:] * to_end).astype(cd)
    z_ref[:] = z_ref[:] * grow + _f32_acc(jax.lax.dot_general, cd)(
        kd, kT_ref[:], _NT)
    return vd


def _state_advance_slab(st_ref, kf_ref, i, s_i, vd, grow):
    """Slab i of the state, read as s_i [dv, j], to the chunk's end."""
    pk = (kf_ref[:] * kf_ref[pl.ds(i, 1), :]).astype(vd.dtype)  # [j, s]
    st_ref[i] = s_i * grow + _f32_acc(jax.lax.dot_general, vd.dtype)(
        vd, pk, _NT)


def _retention_kernel(qT_ref, k_ref, kT_ref, vT_ref, crow_ref, ccol_ref,
                      oT_ref, st_ref, z_ref, qf_ref, kf_ref, acc_ref, *,
                      scale, eps, groups):
    """One (head, chunk) program, feature-major: tokens lie on the lanes,
    so a slab of phi is a sublane-broadcast multiply and the loop over
    the d slabs indexes rows."""
    f32, cd = jnp.float32, qT_ref.dtype
    dot = _f32_acc(jax.lax.dot_general, cd)
    d, c = kT_ref.shape
    crow, to_end, grow = _state_enter(st_ref, z_ref, kf_ref, kT_ref,
                                      crow_ref)
    ccol = ccol_ref[:]                      # [C, 1]
    reach = np.float32(scale * scale) * jnp.exp(crow)
    s_pos = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    t_pos = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decay = jnp.exp(jnp.where(t_pos >= s_pos, crow - ccol, _NEG))   # [s, t]
    z_cd = z_ref[:].astype(cd)
    qf_ref[:] = qT_ref[:].astype(f32)

    # within the chunk: the quadratic form over its own tokens
    dens = []
    for g in range(groups):
        s = dot(k_ref[:], qT_ref[g], _NN) * np.float32(scale)       # [s, t]
        w = s * s * decay
        acc_ref[g] = dot(vT_ref[:], w.astype(cd), _NN)              # [dv, t]
        zq = dot(z_cd, qT_ref[g], _NN)                              # [i, t]
        dens.append(jnp.sum(w, axis=0, keepdims=True) + reach *
                    jnp.sum(zq * qf_ref[g], axis=0, keepdims=True))
    vd = _state_advance_z(z_ref, kf_ref, kT_ref, vT_ref, to_end, grow)

    # the state: query slab i for every head, then advance slab i
    def slab(i, carry):
        s_i = st_ref[i]                                             # [dv, j]
        s_cd = s_i.astype(cd)
        for g in range(groups):
            pq = (qf_ref[g] * qf_ref[g, pl.ds(i, 1), :]).astype(cd)  # [j, t]
            acc_ref[g] += dot(s_cd, pq, _NN) * reach
        _state_advance_slab(st_ref, kf_ref, i, s_i, vd, grow)
        return carry

    jax.lax.fori_loop(np.int32(0), np.int32(d), slab, np.int32(0))
    for g in range(groups):
        oT_ref[g] = (acc_ref[g] / (dens[g] + np.float32(eps))) \
            .astype(oT_ref.dtype)


def _retention_states_kernel(kT_ref, vT_ref, crow_ref, s0_ref, z0_ref,
                             st_ref, z_ref, kf_ref):
    """One (head, chunk) program of the states pass: write the state the
    chunk starts from, then advance it as the forward kernel does.  No
    query is read."""
    _, to_end, grow = _state_enter(st_ref, z_ref, kf_ref, kT_ref, crow_ref)
    z0_ref[:] = z_ref[:].astype(z0_ref.dtype)
    vd = _state_advance_z(z_ref, kf_ref, kT_ref, vT_ref, to_end, grow)

    def slab(i, carry):
        s_i = st_ref[i]
        s0_ref[i] = s_i.astype(s0_ref.dtype)
        _state_advance_slab(st_ref, kf_ref, i, s_i, vd, grow)
        return carry

    jax.lax.fori_loop(np.int32(0), np.int32(kT_ref.shape[0]), slab,
                      np.int32(0))


def _chunk_spec(*block):
    """A (head, chunk) program's block of an operand [bh, n, *block]."""
    zeros = (np.int32(0),) * len(block)
    return pl.BlockSpec((None, None) + block, lambda h, j: (h, j) + zeros)


_RETENTION_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024))


def _retention_pallas(qh, kh, vh, ah, scale, eps, interpret):
    """The forward in Pallas.  Same operands and result as
    ``_retention_scan``; grid (head, chunk), the chunk axis sequential."""
    bh, n, g, c, d = qh.shape
    dv = vh.shape[-1]
    if c % _LANES or d % _LANES or dv % _LANES:
        raise ValueError("power_retention kernel: chunk %d and head sizes "
                         "%d/%d must be multiples of %d"
                         % (c, d, dv, _LANES))
    cs = jnp.cumsum(ah.astype(jnp.float32), axis=-1)        # [bh, n, C]
    kernel = functools.partial(_retention_kernel, scale=scale, eps=eps,
                               groups=g)
    oT = pl.pallas_call(
        kernel,
        grid=(bh, n),
        in_specs=[_chunk_spec(g, d, c), _chunk_spec(c, d),
                  _chunk_spec(d, c), _chunk_spec(dv, c), _chunk_spec(1, c),
                  _chunk_spec(c, 1)],
        out_specs=_chunk_spec(g, dv, c),
        out_shape=jax.ShapeDtypeStruct((bh, n, g, dv, c), qh.dtype),
        scratch_shapes=[
            pltpu.VMEM((d, dv, d), jnp.float32),
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((g, d, c), jnp.float32),
            pltpu.VMEM((d, c), jnp.float32),
            pltpu.VMEM((g, dv, c), jnp.float32),
        ],
        name="power_retention_fwd",
        interpret=interpret,
        **_RETENTION_PARAMS,
    )(jnp.swapaxes(qh, -1, -2), kh, jnp.swapaxes(kh, -1, -2),
      jnp.swapaxes(vh, -1, -2), cs[:, :, None, :], cs[..., None])
    return jnp.swapaxes(oT, -1, -2)


def _retention_states_pallas(kh, vh, ah, interpret):
    """The states pass in Pallas.  Same operands and results as
    ``_retention_states_scan``, the forward kernel's grid."""
    bh, n, c, d = kh.shape
    dv = vh.shape[-1]
    cs = jnp.cumsum(ah.astype(jnp.float32), axis=-1)
    return pl.pallas_call(
        _retention_states_kernel,
        grid=(bh, n),
        in_specs=[_chunk_spec(d, c), _chunk_spec(dv, c), _chunk_spec(1, c)],
        out_specs=[_chunk_spec(d, dv, d), _chunk_spec(d, d)],
        out_shape=[jax.ShapeDtypeStruct((bh, n, d, dv, d), kh.dtype),
                   jax.ShapeDtypeStruct((bh, n, d, d), kh.dtype)],
        scratch_shapes=[
            pltpu.VMEM((d, dv, d), jnp.float32),
            pltpu.VMEM((d, d), jnp.float32),
            pltpu.VMEM((d, c), jnp.float32),
        ],
        name="power_retention_bwd_states",
        interpret=interpret,
        **_RETENTION_PARAMS,
    )(jnp.swapaxes(kh, -1, -2), jnp.swapaxes(vh, -1, -2), cs[:, :, None, :])


def _retention_grads(qh, kh, vh, ah, S0, Z0, do, scale, eps):
    """Backward of the chunked state form: the chunks in reverse, the
    state's cotangent carried from each chunk's end to its start, one
    chunk's own gradients by ``jax.vjp`` of ``_retention_chunk`` from the
    chunk-start state.  One head at a time: a chunk's phi(q) alone
    is G*C*d*d elements.

    A chunk reads its state from the whole S0 and Z0 by (head, chunk)
    index; the loops carry no slice of them.  One head's states fit a
    v5e's VMEM (67 MB at Brumby's widths), and XLA:TPU, given them as a
    loop operand, moved them out to HBM and back in every chunk."""
    f32, i32 = jnp.float32, jnp.int32
    chunk_fn = functools.partial(_retention_chunk, scale=scale, eps=eps)
    bh, n = qh.shape[:2]

    def head(xs):
        h = xs[0]

        def body(carry, ys):
            j, q_c, k_c, v_c, a_c, do_c = ys
            S_c, Z_c = (jax.lax.dynamic_slice(
                x, (h, j) + (np.int32(0),) * (x.ndim - 2),
                (1, 1) + x.shape[2:])[0, 0]
                for x in (S0, Z0))
            _, vjp = jax.vjp(chunk_fn, S_c.astype(f32), Z_c.astype(f32),
                             q_c, k_c, v_c, a_c)
            dS, dZ, dq, dk, dv, da = vjp(carry + (do_c,))
            return (dS, dZ), (dq, dk, dv, da)

        init = (jnp.zeros(S0.shape[2:], f32), jnp.zeros(Z0.shape[2:], f32))
        return jax.lax.scan(body, init, (jnp.arange(n, dtype=i32),) + xs[1:],
                            reverse=True)[1]

    return jax.lax.map(head, (jnp.arange(bh, dtype=i32), qh, kh, vh, ah, do))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def power_retention(q, k, v, log_gate, chunk=128, eps=1e-6, use_kernel=False,
                    interpret=False):
    """Causal power retention of degree 2 in the chunked state form.

    q [B, S, Hq, d], k [B, S, Hkv, d], v [B, S, Hkv, dv], log_gate
    [B, S, Hkv] (float32, <= 0) -> [B, S, Hq, dv]; query head i reads
    key/value head i // (Hq // Hkv); q.k is scaled by 1/sqrt(d).
    ``use_kernel`` runs the forward and the backward's states pass as
    Pallas kernels (TPU, or ``interpret=True``), else as the same
    algorithm in ``jnp``; the backward's gradients scan the chunks in
    reverse in ``jnp`` either way.

    The forward saves its operands alone.  The backward first remakes the
    state at each chunk's start from k, v and the gate — the state's
    advance, 15% of the forward's products at 5 query heads a key/value
    head, bit for bit what the forward held — so nothing of the states
    (at 16,384 tokens, 8 key/value heads of 128 and chunks of 1,024:
    ``S0`` [8, 16, 128, 128, 128] bf16 = 537 MB, ``Z0`` 4 MB a layer)
    lives between the passes.  A recomputation segment keeps the op's
    output (``ops/remat.py``; 168 MB a layer there), so its replay holds
    no retention forward.
    """
    b, s = q.shape[:2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    with jax.named_scope("power_retention_fwd"):
        qh, kh, vh, ah = _retention_heads(q, k, v, log_gate, chunk)
        if use_kernel:
            o = _retention_pallas(qh, kh, vh, ah, scale, eps, interpret)
        else:
            o = _retention_scan(qh, kh, vh, ah, scale, eps)
        return _retention_unlay(o, b, s)


def _retention_fwd(q, k, v, log_gate, chunk, eps, use_kernel, interpret):
    # the primal body itself (``.fun``), then the mark: a trace of the op
    # that is not differentiated marks nothing and counts nothing
    (o,) = _remat.keep(power_retention.fun(q, k, v, log_gate, chunk, eps,
                                           use_kernel, interpret))
    return o, (q, k, v, log_gate)


def _retention_bwd(chunk, eps, use_kernel, interpret, res, do):
    q, k, v, log_gate = res
    b, s = q.shape[:2]
    scale = 1.0 / math.sqrt(q.shape[-1])
    _tel.bump("power_retention_states_traced")
    with jax.named_scope("power_retention_bwd"):
        qh, kh, vh, ah = _retention_heads(q, k, v, log_gate, chunk)
        doh = _retention_heads(do.astype(q.dtype), k, v, log_gate, chunk)[0]
        if use_kernel:
            S0, Z0 = _retention_states_pallas(kh, vh, ah, interpret)
        else:
            S0, Z0 = _retention_states_scan(kh, vh, ah)
        dq, dk, dv, da = _retention_grads(qh, kh, vh, ah, S0, Z0, doh,
                                          scale, eps)
        dq = _retention_unlay(dq, b, s)
        dk, dv, da = (_retention_unlay(x[:, :, None], b, s)
                      for x in (dk, dv, da[..., None]))
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            da[..., 0].astype(log_gate.dtype))


power_retention.defvjp(_retention_fwd, _retention_bwd)


# ---------------------------------------------------------------------------
# State-space scan (Mamba-2's SSD; Dao and Gu, arXiv:2405.21060) in the
# chunked dual form.  For each head, with a_t = dt_t * A <= 0 (A =
# -exp(a_log)), c the running sum of a inside a chunk and a state S [N, P]
# that starts at zero:
#
#   y_t   = sum_{s<=t} exp(c_t - c_s) (C_t . B_s) dt_s x_s    (this chunk)
#           + exp(c_t) C_t S_start + D x_t
#   S_end = exp(c_end) S_start + sum_s exp(c_end - c_s) dt_s B_s x_s^T
#
# dt, a, c, every decay factor and the carried state are float32; a decay
# factor is exp of a difference of running sums, never a ratio of two
# exponentials.  Products take operands in the inputs' dtype and accumulate
# in float32.  B and C belong to a group of heads, so C B^T of a chunk is
# one [Q, Q] product for all of them.

_TN = (((0,), (0,)), ((), ()))


def _ssm_pad(v, length):
    pad = length - v.shape[1]
    return jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2)) \
        if pad else v


def _ssm_prepare(x, dt, a_log, b, c, chunk):
    """The sequence padded with zeros to whole chunks (x = 0 and dt = 0
    come after every real token and reach none) and the running sum of a
    inside each chunk: -> x, dt, the sum [B, Sp, H], b, c, chunks."""
    n = -(-x.shape[1] // chunk)
    x, dt, b, c = (_ssm_pad(v, n * chunk)
                   for v in (x, dt.astype(jnp.float32), b, c))
    a = dt * -jnp.exp(a_log.astype(jnp.float32))
    cs = jnp.cumsum(a.reshape(a.shape[0], n, chunk, -1), axis=2)
    return x, dt, cs.reshape(a.shape), b, c, n


def _ssm_chunks(n, groups, v, heads=True):
    """[B, Sp, H, ..] -> [n, B, Q, G, H // G, ..], chunks first for a scan
    and heads by group; b and c ([B, Sp, G, N]) with ``heads=False``."""
    shape = (groups, v.shape[2] // groups) if heads else (groups,)
    v = v.reshape((v.shape[0], n, v.shape[1] // n) + shape + v.shape[3:])
    return jnp.moveaxis(v, 1, 0)


def _ssm_unchunk(v, heads=True):
    """[n, B, Q, G, R, ..] -> [B, Sp, H, ..] (``heads=False``: no R)."""
    v = jnp.moveaxis(v, 0, 1)
    split = 5 if heads else 4
    return v.reshape((v.shape[0], v.shape[1] * v.shape[2], -1) +
                     v.shape[split:])


def _ssm_decay(cs):
    """exp(c_t - c_s) for s <= t and 0 above: cs [B, Q, G, R] ->
    [B, G, R, t, s]."""
    ch = jnp.moveaxis(cs, 1, -1)
    t = jnp.arange(cs.shape[1])
    return jnp.exp(jnp.where(t[:, None] >= t[None, :],
                             ch[..., :, None] - ch[..., None, :], -jnp.inf))


def _ssm_advance(S, x, dt, cs, b):
    """The state once a chunk, S [B, G, R, N, P] float32."""
    cd = x.dtype
    end = cs[:, -1]
    w = jnp.exp(end[:, None] - cs) * dt
    xw = (x.astype(jnp.float32) * w[..., None]).astype(cd)
    return jnp.exp(end)[..., None, None] * S + \
        _f32_acc(jnp.einsum, cd)("bsgn,bsgrp->bgrnp", b, xw)


def _ssm_chunk(S, x, dt, cs, b, c, d):
    """One chunk of every head in ``jnp``: the state at the chunk's start
    -> (the state at its end, the outputs).  x [B, Q, G, R, P], dt and cs
    [B, Q, G, R], b and c [B, Q, G, N], d [G, R]."""
    f32, cd = jnp.float32, x.dtype
    ein = _f32_acc(jnp.einsum, cd)
    m = _ssm_decay(cs) * ein("btgn,bsgn->bgts", c, b)[:, :, None] * \
        jnp.moveaxis(dt, 1, -1)[..., None, :]
    y = ein("bgrts,bsgrp->btgrp", m.astype(cd), x)
    y = y + jnp.exp(cs)[..., None] * ein("btgn,bgrnp->btgrp", c,
                                         S.astype(cd))
    y = y + d[..., None] * x.astype(f32)
    return _ssm_advance(S, x, dt, cs, b), y.astype(cd)


def _ssm_chunk_grads(S0, dS1, x, dt, cs, b, c, d, dy):
    """Backward of ``_ssm_chunk`` by hand: the state the chunk started
    from (in the operands' dtype), the cotangents of the state at its end
    and of its outputs -> the cotangent of the state at its start and
    (dx, d dt as far as dt enters directly, d cs of the running sum, db,
    dc, dd).

    The running sum's cotangent is a difference that a reverse running
    sum follows (``_ssm_bwd``): entry [t, s] of F = (dy x^T) o decay o
    C B^T adds F dt_s at t and takes it away at s, and only entries with
    s < s0 <= t may remain in the sum at s0.  Both sides are therefore
    taken from the one float32 F, its row and column sums: two roundings
    of the same entry (y itself and a second product, say) would leave
    bf16 noise from every entry of the chunk where exact arithmetic
    leaves nothing, which is larger than the gradient of a head that
    forgets within a few tokens."""
    f32, cd = jnp.float32, x.dtype
    ein = _f32_acc(jnp.einsum, cd)
    xf, dyf = x.astype(f32), dy.astype(f32)
    dt_row = jnp.moveaxis(dt, 1, -1)[..., None, :]
    decay = _ssm_decay(cs)
    g = ein("btgn,bsgn->bgts", c, b)[:, :, None]
    v = ein("bgrts,btgrp->bsgrp", (decay * g).astype(cd), dy)
    end = cs[:, -1]
    e = jnp.exp(end[:, None] - cs)
    w = e * dt
    grow = jnp.exp(end)
    ecs = jnp.exp(cs)[..., None]
    dS1c = dS1.astype(cd)
    bds = ein("bsgn,bgrnp->bsgrp", b, dS1c)
    dx = dt[..., None] * v + d[..., None] * dyf + w[..., None] * bds
    k = ein("btgrp,bsgrp->bgrts", dy, x) * decay
    dg = jnp.sum(k * dt_row, axis=2).astype(cd)
    dye = (dyf * ecs).astype(cd)
    xw = (xf * w[..., None]).astype(cd)
    dc = ein("bgts,bsgn->btgn", dg, b) + ein("btgrp,bgrnp->btgn", dye, S0)
    db = ein("bgts,btgn->bsgn", dg, c) + ein("bsgrp,bgrnp->bsgn", xw, dS1c)
    dS0 = grow[..., None, None] * dS1 + ein("btgn,btgrp->bgrnp", c, dye)
    f = k * g
    rows = jnp.moveaxis(jnp.sum(f * dt_row, axis=-1), -1, 1)
    cols = jnp.moveaxis(jnp.sum(f, axis=-2), -1, 1)
    xb = xf * bds
    direct = cols + e * jnp.sum(xb, axis=-1)
    dcs = rows + jnp.sum(dyf * ecs * ein("btgn,bgrnp->btgrp", c, S0),
                         axis=-1) - dt * direct
    # c_end is the running sum's last entry
    dcs = dcs.at[:, -1].add(
        jnp.sum(w[..., None] * xb, axis=(1, -1)) +
        grow * jnp.sum(S0.astype(f32) * dS1, axis=(-2, -1)))
    dd = jnp.sum(dyf * xf, axis=(0, 1, -1))
    return dS0, (dx.astype(cd), direct, dcs, db, dc, dd)


def _ssm_zero_state(x, b):
    (bsz, _, h, p), (g, n) = x.shape, b.shape[2:]
    return jnp.zeros((bsz, g, h // g, n, p), jnp.float32)


def _ssm_scan(x, dt, cs, b, c, d, n):
    """The chunked form in ``jnp``: a scan over the chunks of every head
    at once.  -> y [B, Sp, H, P]."""
    g = b.shape[2]
    xs = tuple(_ssm_chunks(n, g, v) for v in (x, dt, cs)) + \
        tuple(_ssm_chunks(n, g, v, False) for v in (b, c))
    dg = d.reshape(g, -1)
    y = jax.lax.scan(lambda S, v: _ssm_chunk(S, *v, dg),
                     _ssm_zero_state(x, b), xs)[1]
    return _ssm_unchunk(y)


def _ssm_grads_scan(x, dt, cs, b, c, d, dy, n):
    """The backward in ``jnp``: a states pass (the advance alone, each
    chunk's start kept in the operands' dtype), then the chunks in reverse
    with the state's cotangent carried."""
    g = b.shape[2]
    dg = d.reshape(g, -1)
    xc, dtc, csc, dyc = (_ssm_chunks(n, g, v) for v in (x, dt, cs, dy))
    bc, cc = (_ssm_chunks(n, g, v, False) for v in (b, c))
    zero = _ssm_zero_state(x, b)
    states = jax.lax.scan(
        lambda S, v: (_ssm_advance(S, *v), S.astype(x.dtype)), zero,
        (xc, dtc, csc, bc))[1]

    def body(dS, v):
        S0, x_, dt_, cs_, b_, c_, dy_ = v
        return _ssm_chunk_grads(S0, dS, x_, dt_, cs_, b_, c_, dg, dy_)

    dx, direct, dcs, db, dc, dd = jax.lax.scan(
        body, zero, (states, xc, dtc, csc, bc, cc, dyc), reverse=True)[1]
    return (_ssm_unchunk(dx), _ssm_unchunk(direct), _ssm_unchunk(dcs),
            _ssm_unchunk(db, False), _ssm_unchunk(dc, False),
            dd.sum(0).reshape(-1))


# The Pallas kernels: a program a (batch, chunk, block of heads), the
# chunk axis sequential and the blocks of heads inside it, so that the
# chunk's C B^T is computed once (at a group's first block, into scratch)
# and the float32 state of EVERY head (2 MB at 64 heads of 64 x 128) stays
# in scratch from one chunk to the next.  Inside a program the heads go a
# lane tile at a time: with P = 64 two heads fill 128 lanes, so x, y and
# the state are read, computed and written in aligned [.., 128] tiles, and
# the one product that is a head's own, (decay o C B^T) x_h, is done for
# the tile's heads at once as [M_1 | M_2] @ blockdiag(x_1, x_2), which
# costs the MXU what two 64-wide products would and needs no 64-lane slice.
# A value a head ([Q, 1] or [1, 1]) is spread over its head's lanes by
# ``_ssm_lanes``; dt and the running sum come in as [Q, heads] tiles and
# as their transposes, for the decay matrix's columns and rows.

_SSM_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=100 * 1024 * 1024))


def _ssm_tiling(h, g, p, heads):
    """(heads a lane tile, heads a program) for h heads of p in g groups:
    the most heads a program up to *heads* that divide a group's and fill
    whole lane tiles."""
    per_group = h // g
    r = min(max(_LANES // p, 1), per_group)
    for hb in range(max(r, min(heads, per_group)), r - 1, -1):
        if per_group % hb == 0 and hb % r == 0:
            return r, hb
    raise ValueError("state_space_scan kernel: lane tiles of %d heads do "
                     "not tile %d heads a group" % (r, per_group))


def state_space_kernel_fits(x, b, chunk, heads=32):
    """Whether the Pallas scan takes these shapes: lane tiles of whole
    heads, a state and a chunk of whole lane tiles."""
    p, nstate = x.shape[-1], b.shape[-1]
    try:
        r, _ = _ssm_tiling(x.shape[2], b.shape[2], p, heads)
    except ValueError:
        return False
    return (r * p) % _LANES == 0 and nstate % _LANES == 0 and \
        chunk % _LANES == 0


def _ssm_lanes(cols, p):
    """One value a head over the head's p lanes: cols[i] [rows, 1] ->
    [rows, len(cols) * p]."""
    rows, width = cols[0].shape[0], len(cols) * p
    out = jnp.broadcast_to(cols[-1], (rows, width))
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    for i in range(len(cols) - 2, -1, -1):
        out = jnp.where(lane < np.int32((i + 1) * p), cols[i], out)
    return out


def _ssm_of_head(v, i, p):
    """v [rows, r * p] with every lane outside head i's zeroed."""
    if v.shape[1] == p:
        return v
    lane = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
    mine = (lane >= np.int32(i * p)) & (lane < np.int32((i + 1) * p))
    return jnp.where(mine, v, jnp.zeros_like(v))


def _ssm_block_diag(v, p):
    """v [Q, r * p] -> [r * Q, r * p]: row block i keeps head i's lanes."""
    r = v.shape[1] // p
    return v if r == 1 else jnp.concatenate(
        [_ssm_of_head(v, i, p) for i in range(r)], axis=0)


def _ssm_enter(st_ref, g_ref, b_ref, c_ref, cs_ref, dt_ref, per_group):
    """What the three kernels do first: zero this block's state at the
    first chunk of the grid, C B^T into scratch at a group's first block
    (if *g_ref*); -> (the running sum and dt [Q, hb], decay to the chunk's
    end [Q, hb], over the whole chunk [1, hb], whether the block is its
    group's first)."""
    k = pl.program_id(2)

    @pl.when(pl.program_id(1) == 0)
    def _zero():
        st_ref[k] = jnp.zeros(st_ref.shape[1:], st_ref.dtype)

    first = _i32(jax.lax.rem, k, per_group) == 0
    if g_ref is not None:
        @pl.when(first)
        def _group():
            g_ref[:] = _f32_acc(jax.lax.dot_general, b_ref.dtype)(
                c_ref[:], b_ref[:], _NT)

    cs, dt = cs_ref[:], dt_ref[:]
    q = cs.shape[0]
    end = cs[q - 1:q, :]
    return cs, dt, jnp.exp(end - cs), jnp.exp(end), first


def _ssm_lower(q):
    t = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    return t >= s


def _ssm_fwd_kernel(x_ref, b_ref, c_ref, dt_ref, dtT_ref, cs_ref, csT_ref,
                    d_ref, out_ref, st_ref, g_ref=None, *, p, r, per_group):
    """The forward (out: y [Q, hb * P]) and, without *g_ref*, the states
    pass (out: the state each chunk starts from, [N, hb * P])."""
    f32, cd = jnp.float32, x_ref.dtype
    dot = _f32_acc(jax.lax.dot_general, cd)
    cs, dt, to_end, grow, _ = _ssm_enter(st_ref, g_ref, b_ref, c_ref,
                                         cs_ref, dt_ref, per_group)
    k = pl.program_id(2)
    tw = r * p
    w = to_end * dt
    if g_ref is not None:
        ecs = jnp.exp(cs)
        lower = _ssm_lower(cs.shape[0])
    for i in range(x_ref.shape[1] // tw):
        lanes = slice(i * tw, (i + 1) * tw)
        hs = range(i * r, (i + 1) * r)
        xt = x_ref[:, lanes]
        S = st_ref[k, i]
        if g_ref is None:
            out_ref[:, lanes] = S.astype(out_ref.dtype)
        else:
            ms = [(jnp.exp(jnp.where(lower, cs[:, h:h + 1] -
                                     csT_ref[h:h + 1, :], _NEG)) *
                   g_ref[:] * dtT_ref[h:h + 1, :]).astype(cd) for h in hs]
            y = dot(jnp.concatenate(ms, axis=1), _ssm_block_diag(xt, p),
                    _NN)
            y = y + dot(c_ref[:], S.astype(cd), _NN) * \
                _ssm_lanes([ecs[:, h:h + 1] for h in hs], p)
            y = y + d_ref[:, lanes] * xt.astype(f32)
            out_ref[:, lanes] = y.astype(out_ref.dtype)
        xw = (xt.astype(f32) *
              _ssm_lanes([w[:, h:h + 1] for h in hs], p)).astype(cd)
        st_ref[k, i] = S * _ssm_lanes([grow[:, h:h + 1] for h in hs], p) + \
            dot(b_ref[:], xw, _TN)


def _ssm_bwd_kernel(x_ref, dy_ref, b_ref, c_ref, dt_ref, dtT_ref, cs_ref,
                    csT_ref, d_ref, s0_ref, dx_ref, rows_ref, edw_ref,
                    colsT_ref, db_ref, dc_ref, dd_ref, ds_ref, g_ref, dg_ref,
                    dbacc_ref, dcacc_ref, *, p, r, per_group):
    """One (batch, chunk, block of heads) program of the backward, the
    chunks in reverse (the block maps turn the axis round) with the
    state's cotangent carried in scratch: ``_ssm_chunk_grads`` a lane tile
    of heads at a time.  db and dc gather over a group's blocks of heads
    in scratch and are written at its last.  Of the running sum's
    cotangent the kernel writes what it has as columns ([Q, hb]: the row
    sums of F with everything that enters at t, and exp(c_end - c_s) dw_s)
    and what it has as rows ([hb, Q]: the column sums of F); ``_ssm_bwd``
    puts them together."""
    f32, cd = jnp.float32, x_ref.dtype
    dot = _f32_acc(jax.lax.dot_general, cd)
    cs, dt, to_end, grow, first = _ssm_enter(ds_ref, g_ref, b_ref, c_ref,
                                             cs_ref, dt_ref, per_group)
    k = pl.program_id(2)

    @pl.when(first)
    def _group():
        dg_ref[:] = jnp.zeros_like(dg_ref)
        dbacc_ref[:] = jnp.zeros_like(dbacc_ref)
        dcacc_ref[:] = jnp.zeros_like(dcacc_ref)

    q, tw = cs.shape[0], r * p
    ecs = jnp.exp(cs)
    lower = _ssm_lower(q)
    head_lane = jax.lax.broadcasted_iota(jnp.int32, cs.shape, 1)
    last_row = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == \
        np.int32(q - 1)
    rows_all = jnp.zeros(cs.shape, f32)
    edw_all = jnp.zeros(cs.shape, f32)
    for i in range(x_ref.shape[1] // tw):
        lanes = slice(i * tw, (i + 1) * tw)
        hs = range(i * r, (i + 1) * r)
        xt, dyt = x_ref[:, lanes], dy_ref[:, lanes]
        xf, dyf = xt.astype(f32), dyt.astype(f32)
        dS1 = ds_ref[k, i]
        dS1c = dS1.astype(cd)
        S0 = s0_ref[:, lanes]
        ms, row_sums = [], []
        for n_, h in enumerate(hs):
            dt_row = dtT_ref[h:h + 1, :]
            decay = jnp.exp(jnp.where(lower, cs[:, h:h + 1] -
                                      csT_ref[h:h + 1, :], _NEG))
            ms.append((decay * g_ref[:]).astype(cd))
            kk = dot(_ssm_of_head(dyt, n_, p), xt, _NT) * decay
            dg_ref[:] += kk * dt_row
            f = kk * g_ref[:]
            row_sums.append(jnp.sum(f * dt_row, axis=1, keepdims=True))
            colsT_ref[h:h + 1, :] = jnp.sum(f, axis=0, keepdims=True)
        v = dot(jnp.concatenate(ms, axis=0), _ssm_block_diag(dyt, p), _TN)
        bds = dot(b_ref[:], dS1c, _NN)
        e_l, dt_l, ecs_l, grow_l = (
            _ssm_lanes([a[:, h:h + 1] for h in hs], p)
            for a in (to_end, dt, ecs, grow))
        w_l = e_l * dt_l
        d_l = d_ref[:, lanes]
        dx_ref[:, lanes] = (dt_l * v + d_l * dyf + w_l * bds) \
            .astype(dx_ref.dtype)
        dye_f = dyf * ecs_l
        dye = dye_f.astype(cd)
        dcacc_ref[:] += dot(dye, S0, _NT)
        dbacc_ref[:] += dot((xf * w_l).astype(cd), dS1c, _NT)
        ds_ref[k, i] = grow_l * dS1 + dot(c_ref[:], dye, _TN)
        xb = xf * bds
        carried = dye_f * dot(c_ref[:], S0, _NN)
        tail = jnp.sum(w_l * xb, axis=0, keepdims=True) + \
            grow_l * jnp.sum(S0.astype(f32) * dS1, axis=0, keepdims=True)
        dd_ref[:, lanes] = jnp.sum(dyf * xf, axis=0, keepdims=True)
        for n_, h in enumerate(hs):
            enters, dw, end = (
                jnp.sum(_ssm_of_head(a, n_, p), axis=1, keepdims=True)
                for a in (carried, xb, tail))
            mine = head_lane == np.int32(h)
            rows_all = jnp.where(
                mine, row_sums[n_] + enters +
                jnp.where(last_row, end, np.float32(0.0)), rows_all)
            edw_all = jnp.where(mine, to_end[:, h:h + 1] * dw, edw_all)
    rows_ref[:] = rows_all
    edw_ref[:] = edw_all

    @pl.when(_i32(jax.lax.rem, k, per_group) == np.int32(per_group - 1))
    def _write():
        dg = dg_ref[:].astype(cd)
        dc_ref[:] = (dcacc_ref[:] + dot(dg, b_ref[:], _NN)) \
            .astype(dc_ref.dtype)
        db_ref[:] = (dbacc_ref[:] + dot(dg, c_ref[:], _TN)) \
            .astype(db_ref.dtype)


def _ssm_head_tiles(v, n, hb):
    """[B, Sp, H] -> a tile a (chunk, block of heads), [B, n, H / hb, Q,
    hb], and its transpose [.., hb, Q]."""
    bsz, sp, h = v.shape
    v = v.reshape(bsz, n, sp // n, h // hb, hb)
    return v.transpose(0, 1, 3, 2, 4), v.transpose(0, 1, 3, 4, 2)


def _ssm_from_tiles(t):
    bsz, n, blocks, q, hb = t.shape
    return t.transpose(0, 1, 3, 2, 4).reshape(bsz, n * q, blocks * hb)


def _ssm_specs(n, q, hb, p, nstate, per_group, reverse):
    """Block specs of the grid (batch, chunk, block of heads); *reverse*
    visits the chunks last to first."""
    zero = np.int32(0)

    def chunk(j):
        return np.int32(n - 1) - j if reverse else j

    def group(k):
        return _i32(jax.lax.div, k, per_group)

    return dict(
        seq=pl.BlockSpec((None, q, hb * p),
                         lambda b, j, k: (b, chunk(j), k)),
        grp=pl.BlockSpec((None, q, nstate),
                         lambda b, j, k: (b, chunk(j), group(k))),
        tile=pl.BlockSpec((None, None, None, q, hb),
                          lambda b, j, k: (b, chunk(j), k, zero, zero)),
        tileT=pl.BlockSpec((None, None, None, hb, q),
                           lambda b, j, k: (b, chunk(j), k, zero, zero)),
        skip=pl.BlockSpec((1, hb * p), lambda b, j, k: (zero, k)),
        state=pl.BlockSpec((None, None, nstate, hb * p),
                           lambda b, j, k: (b, chunk(j), zero, k)),
        row=pl.BlockSpec((None, None, 1, hb * p),
                         lambda b, j, k: (b, chunk(j), zero, k)))


def _ssm_operands(x, dt, cs, b, c, d, n, heads, reverse=False):
    """What every kernel reads, the kernels' static facts, the grid's
    block specs (*reverse*: the chunks last to first), the grid and the
    heads a program."""
    bsz, sp, h, p = x.shape
    g, nstate = b.shape[2:]
    r, hb = _ssm_tiling(h, g, p, heads)
    dt_t, dt_T = _ssm_head_tiles(dt, n, hb)
    cs_t, cs_T = _ssm_head_tiles(cs, n, hb)
    operands = (b.reshape(bsz, sp, g * nstate), c.reshape(bsz, sp,
                                                          g * nstate),
                dt_t, dt_T, cs_t, cs_T,
                jnp.repeat(d.astype(jnp.float32), p)[None, :])
    facts = dict(p=p, r=r, per_group=h // g // hb)
    spec = _ssm_specs(n, sp // n, hb, p, nstate, h // g // hb, reverse)
    return operands, facts, spec, (bsz, n, h // hb), hb


def _ssm_pallas(x, dt, cs, b, c, d, n, heads, interpret, states=False):
    """The forward in Pallas: same operands and result as ``_ssm_scan``;
    with *states* the states pass instead: the state each chunk starts
    from, [B, n, N, H * P] in the operands' dtype."""
    bsz, sp, h, p = x.shape
    nstate = b.shape[3]
    operands, facts, spec, grid, hb = _ssm_operands(x, dt, cs, b, c, d, n,
                                                    heads)
    q, tiles = sp // n, hb // facts["r"]
    scratch = [pltpu.VMEM((h // hb, tiles, nstate, facts["r"] * p),
                          jnp.float32)]
    if states:
        out_spec, out_shape = spec["state"], (bsz, n, nstate, h * p)
    else:
        out_spec, out_shape = spec["seq"], (bsz, sp, h * p)
        scratch.append(pltpu.VMEM((q, q), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_ssm_fwd_kernel, **facts), grid=grid,
        in_specs=[spec["seq"], spec["grp"], spec["grp"], spec["tile"],
                  spec["tileT"], spec["tile"], spec["tileT"], spec["skip"]],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, x.dtype),
        scratch_shapes=scratch,
        name="state_space_bwd_states" if states else "state_space_fwd",
        interpret=interpret, **_SSM_PARAMS,
    )(x.reshape(bsz, sp, h * p), *operands)
    return out if states else out.reshape(x.shape)


def _ssm_grads_pallas(x, dt, cs, b, c, d, dy, n, heads, interpret):
    """The backward in Pallas: same operands and results as
    ``_ssm_grads_scan``; the states pass, then the reverse pass."""
    bsz, sp, h, p = x.shape
    g, nstate = b.shape[2:]
    f32 = jnp.float32
    S0 = _ssm_pallas(x, dt, cs, b, c, d, n, heads, interpret, states=True)
    operands, facts, spec, grid, hb = _ssm_operands(x, dt, cs, b, c, d, n,
                                                    heads, reverse=True)
    q, r = sp // n, facts["r"]
    flat = (bsz, sp, h * p)
    tile_shape = jax.ShapeDtypeStruct((bsz, n, h // hb, q, hb), f32)
    group_shape = jax.ShapeDtypeStruct((bsz, sp, g * nstate), f32)
    dx, rows, edw, colsT, db, dc, dd = pl.pallas_call(
        functools.partial(_ssm_bwd_kernel, **facts), grid=grid,
        in_specs=[spec["seq"]] * 2 + [
            spec["grp"], spec["grp"], spec["tile"], spec["tileT"],
            spec["tile"], spec["tileT"], spec["skip"], spec["state"]],
        out_specs=[spec["seq"], spec["tile"], spec["tile"], spec["tileT"],
                   spec["grp"], spec["grp"], spec["row"]],
        out_shape=[jax.ShapeDtypeStruct(flat, x.dtype), tile_shape,
                   tile_shape,
                   jax.ShapeDtypeStruct((bsz, n, h // hb, hb, q), f32),
                   group_shape, group_shape,
                   jax.ShapeDtypeStruct((bsz, n, 1, h * p), f32)],
        scratch_shapes=[
            pltpu.VMEM((h // hb, hb // r, nstate, r * p), f32),
            pltpu.VMEM((q, q), f32), pltpu.VMEM((q, q), f32),
            pltpu.VMEM((q, nstate), f32), pltpu.VMEM((q, nstate), f32)],
        name="state_space_bwd", interpret=interpret, **_SSM_PARAMS,
    )(x.reshape(flat), dy.reshape(flat), *operands, S0)
    direct = _ssm_from_tiles(jnp.swapaxes(colsT, -1, -2)) + \
        _ssm_from_tiles(edw)
    return (dx.reshape(x.shape), direct,
            _ssm_from_tiles(rows) - dt * direct,
            db.reshape(b.shape).astype(b.dtype),
            dc.reshape(c.shape).astype(c.dtype),
            dd.sum((0, 1, 2)).reshape(h, p).sum(-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def state_space_scan(x, dt, a_log, b, c, d, chunk=256, use_kernel=False,
                     interpret=False, heads=32):
    """Mamba-2's selective state-space scan in the chunked dual form.

    x [B, S, H, P], dt [B, S, H] (float32, after the softplus), a_log and
    d [H], b and c [B, S, G, N] -> y [B, S, H, P]; head h reads group
    h // (H // G).  For each head, with a_t = -exp(a_log) dt_t and a state
    S [N, P] that starts at zero: S_t = exp(a_t) S_{t-1} + dt_t b_t x_t^T,
    y_t = c_t S_t + d x_t.  ``use_kernel`` runs the forward, the
    backward's states pass and its reverse pass as Pallas kernels (TPU, or
    ``interpret=True``) of *heads* heads a program, else the same
    algorithm in ``jnp``, a scan over chunks.  *chunk* changes the
    rounding and nothing else.

    The forward saves its operands alone.  The backward first
    remakes the state at each chunk's start from x, dt and b alone (the
    state's advance: one of a head's three products) in the operands'
    dtype, so nothing of the states (at 16,384 tokens, 64 heads of 64 x
    128 and chunks of 256: 67 MB a layer in bf16) lives between the
    passes; then the chunks in reverse.  The [chunks, H, Q, Q] decay
    tensor is never whole: the kernels hold one head's [Q, Q] at a time,
    the ``jnp`` path one chunk's.  A recomputation segment keeps the
    op's output (``ops/remat.py``; 134 MB a layer there), for which alone
    the replay would run the forward again.
    """
    s = x.shape[1]
    with jax.named_scope("state_space_fwd"):
        xp, dtp, cs, bp, cp, n = _ssm_prepare(x, dt, a_log, b, c, chunk)
        df = d.astype(jnp.float32)
        if use_kernel:
            y = _ssm_pallas(xp, dtp, cs, bp, cp, df, n, heads, interpret)
        else:
            y = _ssm_scan(xp, dtp, cs, bp, cp, df, n)
        return y[:, :s]


def _ssm_fwd(x, dt, a_log, b, c, d, chunk, use_kernel, interpret, heads):
    (y,) = _remat.keep(state_space_scan.fun(x, dt, a_log, b, c, d, chunk,
                                            use_kernel, interpret, heads))
    return y, (x, dt, a_log, b, c, d)


def _ssm_bwd(chunk, use_kernel, interpret, heads, res, dy):
    x, dt, a_log, b, c, d = res
    s = x.shape[1]
    f32 = jnp.float32
    _tel.bump("state_space_states_traced")
    with jax.named_scope("state_space_bwd"):
        xp, dtp, cs, bp, cp, n = _ssm_prepare(x, dt, a_log, b, c, chunk)
        dyp = _ssm_pad(dy.astype(x.dtype), n * chunk)
        df = d.astype(f32)
        if use_kernel:
            grads = _ssm_grads_pallas(xp, dtp, cs, bp, cp, df, dyp, n,
                                      heads, interpret)
        else:
            grads = _ssm_grads_scan(xp, dtp, cs, bp, cp, df, dyp, n)
        dx, direct, dcs, db, dc, dd = grads
        # c is the running sum of a = dt * A inside a chunk, A = -exp(a_log)
        A = -jnp.exp(a_log.astype(f32))
        da = jax.lax.cumsum(dcs.reshape(dcs.shape[0], n, chunk, -1), axis=2,
                            reverse=True).reshape(dcs.shape)
        ddt = direct + da * A
        da_log = jnp.sum(da * dtp, axis=(0, 1)) * A
    return (dx[:, :s], ddt[:, :s].astype(dt.dtype),
            da_log.astype(a_log.dtype), db[:, :s].astype(b.dtype),
            dc[:, :s].astype(c.dtype), dd.astype(d.dtype))


state_space_scan.defvjp(_ssm_fwd, _ssm_bwd)


# ---------------------------------------------------------------------------
# The sequence convolution (``ops/lm.py``: ``_contrib_CausalConv1D`` and
# ``_contrib_ShortConv``): K taps along the sequence, depthwise, with what
# each op does right before and after them, in one pass over the data.
#
# Channels on lanes, the sequence on sublanes, a program a (batch, channel
# tile, sequence tile).  A tile is loaded once in the data's dtype and cast
# to float32 in VMEM; the K - 1 tokens before it come as a second, small
# block of the same operand (the ``_CONV_HALO`` rows that end where the
# tile starts; zeros before the sequence), so no padded copy exists and the
# forward's programs do not depend on each other.  The term of tap j,
# x_{t-K+1+j}, is rows ``halo - (K-1-j) ..`` of [halo rows | tile]: a
# sublane roll and an aligned slice (``_conv_window``).  The backward walks
# the sequence tiles last to first: it remakes the pre-activation from x as
# the forward made it, forms g = dy * act'(c), keeps the first rows of g in
# scratch for the tile before (dx_t reads g_{t+1..t+K-1}), and gathers dw
# and dbias over the sequence axis in a float32 block that stays in VMEM.
# The gated op's Bg, Cg and u, and a plain op's input inside a wider tensor
# (``begin``), are column blocks of the one operand in the block maps: no
# split and no slice is materialised.
#
# Weight and bias reach the kernels as one float32 [16, C] block, rows
# 0..K-1 the taps, row K the bias (zeros without one: adding them changes
# nothing); the backward's dw and dbias leave in the same layout, a block a
# batch row, summed over the batch outside.

_CONV_ROWS = 512       # tokens a tile
_CONV_LANES = 512      # most channels a tile
_CONV_HALO = 16        # rows of the block before a tile: a bf16 sublane tile
_CONV_PARAMS_ROWS = 16  # rows of the taps-and-bias block: K + 1 <= 9
_CONV_VMEM_BYTES = 100 * 1024 * 1024


def causal_conv_kernel_fits(seq, channels, taps):
    """Whether the convolution kernels take these shapes: whole lane
    tiles of channels, whole sequence tiles, taps inside the halo and
    (with the bias) inside the parameters' block."""
    return channels % _LANES == 0 and seq % _CONV_ROWS == 0 and \
        1 <= taps <= 8


def causal_conv_reads_in_place(channels, begin):
    """Whether the kernels' block maps can start at column *begin* of a
    wider tensor: at a whole channel tile.  Elsewhere they are handed a
    slice."""
    return begin % _conv_lanes(channels) == 0


def _conv_lanes(channels):
    """The widest channel tile up to ``_CONV_LANES`` that divides."""
    return max(t for t in range(_LANES, min(channels, _CONV_LANES) + 1,
                                _LANES) if channels % t == 0)


def _conv_window(z, at, rows):
    """z[at:at + rows] for any *at*: a sublane roll, then an aligned
    slice."""
    if at % 8 == 0:
        return z[at:at + rows]
    return pltpu.roll(z, np.int32(z.shape[0] - at), 0)[:rows]


def _conv_operand(refs, gated, first):
    """What the taps read, float32 [halo + rows, lanes] — the rows before
    the tile (zeros where the tile is the sequence's *first*), then the
    tile; for the gated op Bg * u, rounded as the data is — and the three
    gates' tiles in float32 (None, None, None without gates)."""
    f32 = jnp.float32
    if gated:
        bg_ref, cg_ref, u_ref, bg_halo, u_halo = refs
        bg, u = bg_ref[:].astype(f32), u_ref[:].astype(f32)
        cur = (bg * u).astype(bg_ref.dtype).astype(f32)
        halo = (bg_halo[:].astype(f32) * u_halo[:].astype(f32)) \
            .astype(bg_ref.dtype).astype(f32)
        gates = (bg, cg_ref[:].astype(f32), u)
    else:
        x_ref, x_halo = refs
        cur, halo = x_ref[:].astype(f32), x_halo[:].astype(f32)
        gates = (None, None, None)
    halo = jnp.where(first, jnp.zeros_like(halo), halo)
    return jnp.concatenate([halo, cur], axis=0), gates


def _conv_preact(z, wb_ref, taps, rows):
    """(the K shifted terms of the tile's rows, their weighted sum plus
    the bias)."""
    terms = [_conv_window(z, _CONV_HALO - (taps - 1 - j), rows)
             for j in range(taps)]
    c = terms[0] * wb_ref[0:1, :]
    for j in range(1, taps):
        c = c + terms[j] * wb_ref[j:j + 1, :]
    return terms, c + wb_ref[taps:taps + 1, :]


def _conv_fwd_kernel(*refs, taps, silu, gated):
    *ins, wb_ref, o_ref = refs
    f32 = jnp.float32
    z, (_, cg, _) = _conv_operand(ins, gated, pl.program_id(2) == 0)
    _, c = _conv_preact(z, wb_ref, taps, o_ref.shape[0])
    if silu:
        c = c * jax.nn.sigmoid(c)
    out = c.astype(o_ref.dtype)
    if gated:
        out = (cg * out.astype(f32)).astype(o_ref.dtype)
    o_ref[:] = out


def _conv_bwd_kernel(*refs, taps, silu, gated, tiles):
    """One (batch, channel tile, sequence tile, part) program of the
    backward, the sequence tiles last to first.  Part 0 does the work and
    writes dx (dBg for the gated op, whose dCg and du wait in scratch and
    are copied out by parts 1 and 2 into their column blocks of the one
    [B, S, 3C] gradient)."""
    f32 = jnp.float32
    n_in = 5 if gated else 2
    ins, (dy_ref, wb_ref, dx_ref, acc_ref, g_head), held = \
        refs[:n_in], refs[n_in:n_in + 5], refs[n_in + 5:]
    step, part = pl.program_id(2), pl.program_id(3)
    rows, cd = dx_ref.shape[0], dx_ref.dtype

    @pl.when(part == 0)
    def _work():
        @pl.when(step == 0)
        def _start():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            g_head[:] = jnp.zeros_like(g_head)

        z, (bg, cg, u) = _conv_operand(
            ins, gated, step == np.int32(tiles - 1))
        terms, c = _conv_preact(z, wb_ref, taps, rows)
        g = dy_ref[:].astype(f32)
        if gated:
            a = c * jax.nn.sigmoid(c) if silu else c
            held[0][:] = (g * a.astype(cd).astype(f32)).astype(cd)
            g = (g * cg).astype(cd).astype(f32)
        if silu:
            sig = jax.nn.sigmoid(c)
            g = g * (sig * (np.float32(1.0) + c * (np.float32(1.0) - sig)))
        for j in range(taps):
            acc_ref[j:j + 1, :] += jnp.sum(g * terms[j], axis=0,
                                           keepdims=True)
        acc_ref[taps:taps + 1, :] += jnp.sum(g, axis=0, keepdims=True)
        zg = jnp.concatenate([g, g_head[:]], axis=0)
        g_head[:] = g[:_CONV_HALO]
        dx = _conv_window(zg, taps - 1, rows) * wb_ref[0:1, :]
        for j in range(1, taps):
            dx = dx + _conv_window(zg, taps - 1 - j, rows) * \
                wb_ref[j:j + 1, :]
        if gated:
            dx = dx.astype(cd).astype(f32)
            held[1][:] = (dx * bg).astype(cd)
            dx = dx * u
        dx_ref[:] = dx.astype(cd)

    for n, ref in enumerate(held):
        @pl.when(part == np.int32(n + 1))
        def _copy(ref=ref):
            dx_ref[:] = ref[:]


def _conv_specs(rows, lanes, blocks, gated, seq_tile, first):
    """Block specs of the data's tiles, the halos before them, the
    parameters' block and a [B, S, C] tensor's tiles, over a grid whose
    first three axes are (batch, channel tile, sequence step); *seq_tile*
    maps a step to its tile, *blocks* is the channel tiles a gate (Bg, Cg
    and u are column blocks 0, 1 and 2 of the gated op's data), *first*
    the channel tile of the data's tensor at which the input starts."""
    per = rows // _CONV_HALO

    def tile(at):
        return pl.BlockSpec(
            (None, rows, lanes), lambda b, c, j, *_: (
                b, seq_tile(j), c + np.int32(at)))

    def halo(at):
        return pl.BlockSpec(
            (None, _CONV_HALO, lanes), lambda b, c, j, *_: (
                b, jnp.maximum(seq_tile(j) * np.int32(per) - np.int32(1),
                               np.int32(0)), c + np.int32(at)))

    data = [tile(0), tile(blocks), tile(2 * blocks), halo(0),
            halo(2 * blocks)] if gated else [tile(first), halo(first)]
    params = pl.BlockSpec((_CONV_PARAMS_ROWS, lanes),
                          lambda b, c, *_: (np.int32(0), c))
    return data, params, tile(0)


def _conv_params(weight, bias):
    """float32 [16, C]: rows 0..K-1 the taps, row K the bias."""
    channels, taps = weight.shape
    f32 = jnp.float32
    return jnp.concatenate([
        weight.astype(f32).T, bias.astype(f32)[None, :],
        jnp.zeros((_CONV_PARAMS_ROWS - taps - 1, channels), f32)], axis=0)


def _causal_conv_fwd_impl(data, weight, bias, silu, gated, begin,
                          interpret):
    """The forward in Pallas: data [B, S, W] whose channels begin ..
    begin + C are the input (gated: [B, S, 3C] holding Bg, Cg, u), weight
    [C, K], bias [C] -> [B, S, C] in the data's dtype."""
    rows = _CONV_ROWS
    bsz, seq = data.shape[:2]
    channels, taps = weight.shape
    lanes = _conv_lanes(channels)
    specs, params, out = _conv_specs(rows, lanes, channels // lanes, gated,
                                     lambda j: j, begin // lanes)
    return pl.pallas_call(
        functools.partial(_conv_fwd_kernel, taps=taps, silu=silu,
                          gated=gated),
        grid=(bsz, channels // lanes, seq // rows),
        in_specs=specs + [params], out_specs=out,
        out_shape=jax.ShapeDtypeStruct((bsz, seq, channels), data.dtype),
        name="causal_conv_fwd", interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel"),
            vmem_limit_bytes=_CONV_VMEM_BYTES),
    )(*[data] * len(specs), _conv_params(weight, bias))


def _causal_conv_bwd_impl(data, weight, bias, dy, silu, gated, begin,
                          interpret):
    """The backward in Pallas: -> (d input [B, S, C] (gated: d data
    [B, S, 3C]), d weight, d bias), reading the forward's operands and dy
    once."""
    rows = _CONV_ROWS
    bsz, seq = data.shape[:2]
    channels, taps = weight.shape
    lanes = _conv_lanes(channels)
    blocks, tiles = channels // lanes, seq // rows
    parts = 3 if gated else 1

    def seq_tile(j):
        return np.int32(tiles - 1) - j

    specs, params, like_out = _conv_specs(rows, lanes, blocks, gated,
                                          seq_tile, begin // lanes)
    grad = pl.BlockSpec(
        (None, rows, lanes), lambda b, c, j, part: (
            b, seq_tile(j), part * np.int32(blocks) + c))
    acc = pl.BlockSpec((None, _CONV_PARAMS_ROWS, lanes),
                       lambda b, c, j, part: (b, np.int32(0), c))
    ddata, sums = pl.pallas_call(
        functools.partial(_conv_bwd_kernel, taps=taps, silu=silu,
                          gated=gated, tiles=tiles),
        grid=(bsz, blocks, tiles, parts),
        in_specs=specs + [like_out, params], out_specs=[grad, acc],
        out_shape=[jax.ShapeDtypeStruct((bsz, seq, parts * channels),
                                        data.dtype),
                   jax.ShapeDtypeStruct((bsz, _CONV_PARAMS_ROWS, channels),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_CONV_HALO, lanes), jnp.float32)] +
        [pltpu.VMEM((rows, lanes), data.dtype)] * (parts - 1),
        name="causal_conv_bwd", interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_CONV_VMEM_BYTES),
    )(*[data] * len(specs), dy, _conv_params(weight, bias))
    sums = sums.sum(0)
    return ddata, sums[:taps].T.astype(weight.dtype), \
        sums[taps].astype(bias.dtype)
