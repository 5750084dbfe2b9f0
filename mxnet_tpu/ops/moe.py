"""Sparse experts: dropless top-k routing and a grouped matrix product.

No reference counterpart (the reference's op corpus predates routed
experts).  ``_contrib_SparseMoE`` is a graph op, so a sparse-expert
language model is a ``Symbol`` and trains through ``Module.fit``
(``docs/LM_OPS.md`` has the equations).

Static shapes without a capacity: the ``tokens x k`` routed rows are
ordered by expert and the expert products run over contiguous groups of
data-dependent size.  On a TPU, with whole tiles of 512 rows, the grouped
product is JAX's Pallas kernel (``jax.experimental.pallas.ops.tpu.megablox``:
``gmm``, ``tgmm``), elsewhere ``jax.lax.ragged_dot``, which XLA:TPU also
compiles to a grouped kernel of its own (``ragged-dot-none``) — at the
LFM2 shapes that one measured 4.9-5.4 ms a product where the Pallas kernel
takes 2.6-3.5 (PERF.md section 6, PR 31).  Every token keeps all k of its
experts whatever the load.  The op is told which experts it holds
(``first_expert`` and the leading axis of the stacked tensors): it routes
over all ``num_experts``, computes what the held experts give for the rows
routed to them, and leaves out the rest.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _tel
from .registry import register

_DIMS = jax.lax.RaggedDotDimensionNumbers


def topk_route(scores, k, bias=None, normalise=True, scale=1.0):
    """The tree's one top-k routing routine.  *scores* [tokens, experts],
    float32: the experts of a token are the top k of ``scores + bias``,
    their weights are ``scores`` at those experts (without the bias),
    divided by their sum + 1e-6 if *normalise*, times *scale*.  Returns
    (expert ids [tokens, k] int32, weights [tokens, k] float32); ties go
    to the lower id."""
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, k)
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if normalise:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + 1e-6)
    return idx.astype(jnp.int32), weight * scale


_TILE_ROWS = 512


def _kernel_backend():
    return jax.default_backend() == "tpu"


def _pallas_grouped(rows):
    """Whether the grouped products of *rows* routed rows run as the
    Pallas kernels: on a TPU, in whole row tiles."""
    return _kernel_backend() and rows % _TILE_ROWS == 0


def _half(n):
    """A tile half as wide as n where that is still whole lanes."""
    return n // 2 if n % 256 == 0 else n


def _grouped(rows, stack, sizes, live=None, transpose=False):
    """rows [R, a] times the group's matrix of *stack* [G, a, b] ([G, b, a]
    with *transpose*) -> [R, b].  Rows past the last group are in no
    product: *live* [R, 1] zeroes what the kernel leaves there."""
    if _pallas_grouped(rows.shape[0]):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        a, b = rows.shape[1], stack.shape[1 if transpose else 2]
        # tiles (rows, contracted, out) from a sweep on the chip at
        # a, b = 2048, 1792: the whole contraction in one tile when the
        # stack is read as stored, 1024 of it when read transposed
        tiling = (_TILE_ROWS, 1024 if transpose else a, _half(b))
        # the kernel's own index arithmetic is written for 32-bit JAX
        with jax.enable_x64(False):
            out = gmm(rows, stack, sizes, rows.dtype, tiling,
                      transpose_rhs=transpose)
    else:
        # XLA:TPU's grouped kernel takes [G, contracted, out] only; given
        # the other dimension numbers it falls back to one dense product
        # a group, so the transpose is made in memory
        if transpose:
            stack = jnp.swapaxes(stack, 1, 2)
        out = jax.lax.ragged_dot(rows, stack, sizes,
                                 preferred_element_type=rows.dtype)
    return out if live is None else jnp.where(live, out, 0)


def _grouped_outer(lhs, rhs, sizes, dtype):
    """sum over each group's rows of lhs^T rhs: [R, a], [R, b] -> [G, a, b]
    (the stacked tensors' gradient; zeros for a group without rows)."""
    if _pallas_grouped(lhs.shape[0]):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
        tiling = (_TILE_ROWS, 1024, _half(rhs.shape[1]))
        with jax.enable_x64(False):
            return tgmm(lhs.T, rhs, sizes, dtype, tiling)
    dims = _DIMS((([0], [0]), ([], [])), [0], [])
    return jax.lax.ragged_dot_general(lhs, rhs, sizes, dims,
                                      preferred_element_type=dtype)


def _gate(h1, h3):
    """(silu(h1) * h3, sigmoid(h1)) in float32."""
    h1, h3 = h1.astype(jnp.float32), h3.astype(jnp.float32)
    sig = jax.nn.sigmoid(h1)
    return h1 * sig * h3, sig


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _experts(x, w1, w3, w2, weight, order, inverse, sizes, partial_share):
    """sum over a token's k choices of weight * (silu(h W1_e) * (h W3_e))
    W2_e.  x [T, H]; weight [T, k] float32; *order* [T k]: the flat
    (token, choice) pair at each row of the expert-sorted order, *inverse*
    its inverse; *sizes* [G]: rows of each held expert.  With
    *partial_share* the rows past sum(sizes) belong to experts held
    elsewhere and add nothing."""
    return _experts_fwd(x, w1, w3, w2, weight, order, inverse, sizes,
                        partial_share)[0]


def _rows(x, index):
    """x[index] along axis 0 for an index known to be in bounds (a
    permutation, or one divided by k): no fill pass over the result."""
    return jnp.take(x, index, axis=0, mode="clip")


def _sorted_rows(x, weight, order, sizes, partial_share):
    """(x's row, the weight, whether an expert held here takes it) at each
    row of the sorted order."""
    k = weight.shape[1]
    xs = _rows(x, order // k)
    ws = _rows(weight.reshape(-1), order)
    live = None
    if partial_share:
        live = (jnp.arange(ws.shape[0]) < jnp.sum(sizes))[:, None]
        ws = jnp.where(live[:, 0], ws, 0.0)
    return xs, ws, live


def _combine(y, inverse, k):
    """sum over a token's k choices of its rows of y (sorted order), in
    float32: k gathers of [T, H] added, no [T, k, H] tensor."""
    where = inverse.reshape(-1, k)
    return sum(_rows(y, where[:, j]).astype(jnp.float32) for j in range(k))


def _experts_fwd(x, w1, w3, w2, weight, order, inverse, sizes,
                 partial_share):
    k = weight.shape[1]
    with jax.named_scope("moe_experts_fwd"):
        xs, ws, live = _sorted_rows(x, weight, order, sizes, partial_share)
        h1 = _grouped(xs, w1, sizes, live)
        h3 = _grouped(xs, w3, sizes, live)
        # the combine's weight goes in before the last product, which is
        # linear in its rows: the backward then needs no copy of its output
        aw = (_gate(h1, h3)[0] * ws[:, None]).astype(x.dtype)
        y = _grouped(aw, w2, sizes, live)
        out = _combine(y, inverse, k).astype(x.dtype)
    return out, (xs, ws, live, w1, w3, w2, order, inverse, sizes, h1, h3)


def _experts_bwd(partial_share, res, dout):
    xs, ws, live, w1, w3, w2, order, inverse, sizes, h1, h3 = res
    k = inverse.shape[0] // dout.shape[0]
    dt = xs.dtype
    with jax.named_scope("moe_experts_bwd"):
        g = _rows(dout.astype(dt), order // k)
        a, sig = _gate(h1, h3)
        daw = _grouped(g, w2, sizes, live, transpose=True) \
            .astype(jnp.float32)
        dws = jnp.sum(daw * a, axis=-1)
        da = daw * ws[:, None]
        h1f, h3f = h1.astype(jnp.float32), h3.astype(jnp.float32)
        dh1 = (da * h3f * sig * (1.0 + h1f * (1.0 - sig))).astype(dt)
        dh3 = (da * h1f * sig).astype(dt)
        dxs = (_grouped(dh1, w1, sizes, live, transpose=True)
               .astype(jnp.float32) +
               _grouped(dh3, w3, sizes, live, transpose=True)
               .astype(jnp.float32)).astype(dt)
        dw1 = _grouped_outer(xs, dh1, sizes, w1.dtype)
        dw3 = _grouped_outer(xs, dh3, sizes, w3.dtype)
        dw2 = _grouped_outer((a * ws[:, None]).astype(dt), g, sizes,
                             w2.dtype)
        dx = _combine(dxs, inverse, k).astype(dt)
        dweight = _rows(dws, inverse).reshape(-1, k)
    return dx, dw1, dw3, dw2, dweight, None, None, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def sparse_moe(x, router_weight, w1, w3, w2, expert_bias, num_experts,
               num_experts_per_tok, first_expert=0, scoring="sigmoid",
               norm_topk_prob=True, routed_scaling_factor=1.0):
    """(the held experts' part of the layer's result, the k expert ids of
    every token as float32).  x [..., H]; router_weight [num_experts, H];
    w1, w3 [held, H, I] and w2 [held, I, H] for the experts
    ``first_expert .. first_expert + held - 1``; expert_bias
    [num_experts], added to the scores for the choice only."""
    k, held = int(num_experts_per_tok), w1.shape[0]
    if router_weight.shape[0] != int(num_experts) or \
            first_expert + held > int(num_experts):
        raise ValueError(
            "sparse_moe: router of %d experts, %d held from %d, "
            "num_experts %s" % (router_weight.shape[0], held, first_expert,
                                num_experts))
    flat = x.reshape(-1, x.shape[-1])
    rows = flat.shape[0] * k
    with jax.named_scope("moe_route"):
        logits = jnp.dot(flat.astype(jnp.float32),
                         router_weight.astype(jnp.float32).T,
                         precision=jax.lax.Precision.HIGHEST)
        if scoring == "sigmoid":
            scores = jax.nn.sigmoid(logits)
        elif scoring == "softmax":
            scores = jax.nn.softmax(logits, axis=-1)
        else:
            raise ValueError("sparse_moe: scoring %r (sigmoid, softmax)"
                             % (scoring,))
        idx, weight = topk_route(
            scores, k, jax.lax.stop_gradient(expert_bias.astype(jnp.float32)),
            bool(norm_topk_prob), float(routed_scaling_factor))
        local = idx.reshape(-1) - int(first_expert)
        # rows of experts held elsewhere sort behind every group
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        # a compare and a sum: XLA:TPU's scatter-add (bincount) took 3.9 ms
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
    _tel.bump("sparse_moe_traced")
    _tel.bump("sparse_moe_rows", rows)
    out = _experts(flat, w1, w3, w2, weight, order, inverse, sizes,
                   held < int(num_experts))
    return out.reshape(x.shape), \
        idx.reshape(x.shape[:-1] + (k,)).astype(jnp.float32)


@register("_contrib_SparseMoE", aliases=["SparseMoE"], num_outputs=2,
          nondiff_inputs=(5,))
def _sparse_moe(data, router_weight, w1, w3, w2, expert_bias,
                num_experts=None, num_experts_per_tok=1, first_expert=0,
                scoring="sigmoid", norm_topk_prob=True,
                routed_scaling_factor=1.0, **kw):
    """Dropless sparse-expert SwiGLU layer: output 0 is the held experts'
    part of the result, output 1 the chosen expert ids [..., k] (float32,
    no gradient)."""
    out, choice = sparse_moe(
        data, router_weight, w1, w3, w2, expert_bias, int(num_experts),
        int(num_experts_per_tok), int(first_expert), scoring,
        norm_topk_prob, routed_scaling_factor)
    return out, jax.lax.stop_gradient(choice)
