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

What a partial share costs: the sort puts the held experts' rows first,
and the gathers, the gates, the grouped products' buffers and every
backward temporary are ``_share_rows`` long — half as much again as the
expected ``tokens x k x held / num_experts``, in whole row tiles — not
``tokens x k``.  That is a bound on a buffer, not a capacity: when a
step's held rows exceed it the same chunk program runs again on the next
rows of the sorted order (a loop that the step's ``sizes`` end), so no
row is ever dropped; a chunk stops after the last expert that fits in it
whole (``_span``), so an expert's gradient is one grouped product's and
adding the chunks' parts rounds nothing; only the first chunk's
activations are kept for the backward, a later chunk's are computed
again there.  The combine still reads ``tokens x k`` row indices (k
gathers of ``[tokens, H]``).  With every expert held there is one chunk
of ``tokens x k`` rows and no mask: the program PR 31 measured.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .. import telemetry as _tel
from . import remat as _remat
from .registry import register

_DIMS = jax.lax.RaggedDotDimensionNumbers


def topk_route(scores, k, bias=None, normalise=True, scale=1.0, eps=1e-6):
    """The tree's one top-k routing routine.  *scores* [tokens, experts],
    float32: the experts of a token are the top k of ``scores + bias``,
    their weights are ``scores`` at those experts (without the bias),
    divided by their sum + *eps* if *normalise*, times *scale*.  Returns
    (expert ids [tokens, k] int32, weights [tokens, k] float32); ties go
    to the lower id.  A recomputation segment keeps the ids
    (``ops/remat.py``), so its replay gathers the weights from scores it
    computes again but chooses nothing again."""
    _, idx = jax.lax.top_k(scores if bias is None else scores + bias, k)
    (idx,) = _remat.keep(idx.astype(jnp.int32))
    weight = jnp.take_along_axis(scores, idx, axis=-1)
    if normalise:
        weight = weight / (jnp.sum(weight, axis=-1, keepdims=True) + eps)
    return idx, weight * scale


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


_SHARE_ROOM = 1.5       # a chunk of a partial share: this x the expected rows


def _share_rows(rows, held, num_experts):
    """Rows of one chunk of the sorted order when *held* of *num_experts*
    experts are held: ``_SHARE_ROOM`` times the share uniform routing
    gives, in whole row tiles where the routed rows are, never more than
    all of them."""
    unit = _TILE_ROWS if rows % _TILE_ROWS == 0 else 1
    want = -(-int(_SHARE_ROOM * rows * held) // (num_experts * unit)) * unit
    return min(rows, max(want, unit))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def _experts(x, w1, w3, w2, weight, order, inverse, sizes, chunk):
    """sum over a token's k choices of weight * (silu(h W1_e) * (h W3_e))
    W2_e.  x [T, H]; weight [T, k] float32; *order* [T k]: the flat
    (token, choice) pair at each row of the expert-sorted order, *inverse*
    its inverse; *sizes* [G]: rows of each held expert.  *chunk* is None
    when every expert is held.  Otherwise the rows past sum(sizes) belong
    to experts held elsewhere and add nothing, and the sorted order is
    worked through *chunk* rows at a time, as far as sum(sizes) reaches."""
    return _experts_fwd(x, w1, w3, w2, weight, order, inverse, sizes,
                        chunk)[0]


def _rows(x, index):
    """x[index] along axis 0 for an index known to be in bounds (a
    permutation, or one divided by k): no fill pass over the result."""
    return jnp.take(x, index, axis=0, mode="clip")


def _span(sizes, start, n):
    """(where the chunk that starts at row *start* of the sorted order
    stops, the rows of each held expert inside it).  A chunk holds at
    most *n* rows and stops after the last expert that still fits whole,
    so that no expert's rows are split over two chunks and its stacks'
    gradient is summed in one grouped product; only an expert with more
    than *n* rows of its own is taken *n* rows at a time."""
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    whole = jnp.max(jnp.where(ends <= start + n, ends, 0))
    stop = jnp.where(whole > start, whole, jnp.minimum(start + n, ends[-1]))
    inside = jnp.clip(ends, start, stop) - jnp.clip(ends - sizes, start, stop)
    return stop, inside


def _sorted_rows(x, weight, order, sizes, chunk, start=0):
    """(x's row, the weight, whether an expert held here takes it, the
    held experts' rows, the flat (token, choice) pair) at each row of the
    chunk of the sorted order that starts at row *start*, and where the
    chunk stops."""
    k = weight.shape[1]
    if chunk is None:
        head, live, stop = order, None, order.shape[0]
    else:
        head = jax.lax.dynamic_slice(jnp.pad(order, (0, chunk)), (start,),
                                     (chunk,))
        stop, sizes = _span(sizes, start, chunk)
        live = (jnp.arange(chunk) < stop - start)[:, None]
    xs = _rows(x, head // k)
    ws = _rows(weight.reshape(-1), head)
    if live is not None:
        ws = jnp.where(live[:, 0], ws, 0.0)
    return (xs, ws, live, sizes.astype(jnp.int32), head), stop


def _from_chunk(y, where, chunk, start):
    """y's rows at the sorted positions *where*, zero for a position
    outside the chunk that starts at *start*."""
    if chunk is None:
        return _rows(y, where)
    where = where - start
    inside = (where >= 0) & (where < chunk)
    return jnp.where(inside.reshape(inside.shape + (1,) * (y.ndim - 1)),
                     _rows(y, where), 0)


def _combine(y, inverse, k, chunk=None, start=0):
    """sum over a token's k choices of its rows of y (the chunk of the
    sorted order that starts at *start*), in float32: k gathers of [T, H]
    added, no [T, k, H] tensor."""
    where = inverse.reshape(-1, k)
    return sum(_from_chunk(y, where[:, j], chunk, start).astype(jnp.float32)
               for j in range(k))


def _chunk_fwd(x, w1, w3, w2, weight, order, sizes, chunk, start=0):
    """(the chunk's rows of the last product, what its backward reads,
    where the chunk stops)."""
    rows, stop = _sorted_rows(x, weight, order, sizes, chunk, start)
    xs, ws, live, sizes, _ = rows
    h1 = _grouped(xs, w1, sizes, live)
    h3 = _grouped(xs, w3, sizes, live)
    # the combine's weight goes in before the last product, which is
    # linear in its rows: the backward then needs no copy of its output
    aw = (_gate(h1, h3)[0] * ws[:, None]).astype(x.dtype)
    return _grouped(aw, w2, sizes, live), rows + (h1, h3), stop


def _later_chunks(chunk, order, sizes, stop, body, init):
    """*init* after ``body(start, value)`` for every chunk past the first
    (which stopped at *stop*) that the held experts' rows reach into:
    none, in a step whose held rows fit the first chunk.  *body* returns
    (where its chunk stops, the new value)."""
    if chunk is None or chunk >= order.shape[0]:
        return init
    total = jnp.sum(sizes).astype(jnp.int32)
    return jax.lax.while_loop(lambda state: state[0] < total,
                              lambda state: body(*state), (stop, init))[1]


def _experts_fwd(x, w1, w3, w2, weight, order, inverse, sizes, chunk):
    k = weight.shape[1]
    with jax.named_scope("moe_experts_fwd"):
        y, kept, stop = _chunk_fwd(x, w1, w3, w2, weight, order, sizes,
                                   chunk)

        def more(start, out):
            y, _, stop = _chunk_fwd(x, w1, w3, w2, weight, order, sizes,
                                    chunk, start)
            return stop, out + _combine(y, inverse, k, chunk, start)

        out = _later_chunks(chunk, order, sizes, stop, more,
                            _combine(y, inverse, k, chunk))
    return out.astype(x.dtype), (x, weight, kept, w1, w3, w2, order, inverse,
                                 sizes)


def _chunk_bwd(dout, kept, w1, w3, w2, inverse, k, chunk, start=0):
    """One chunk's part of (dx [T, H] float32, dw1, dw3, dw2, dweight
    [T k] float32) from what ``_chunk_fwd`` kept."""
    xs, ws, live, sizes, head, h1, h3 = kept
    dt = xs.dtype
    g = _rows(dout.astype(dt), head // k)
    a, sig = _gate(h1, h3)
    daw = _grouped(g, w2, sizes, live, transpose=True).astype(jnp.float32)
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
    dw2 = _grouped_outer((a * ws[:, None]).astype(dt), g, sizes, w2.dtype)
    return (_combine(dxs, inverse, k, chunk, start), dw1, dw3, dw2,
            _from_chunk(dws, inverse, chunk, start))


def _experts_bwd(chunk, res, dout):
    x, weight, kept, w1, w3, w2, order, inverse, sizes = res
    k = weight.shape[1]
    with jax.named_scope("moe_experts_bwd"):
        # a later chunk's activations were not kept and are computed
        # again.  An expert's rows are in one chunk (``_span``), so of the
        # two parts of a stack's gradient added here one is zero for every
        # expert and the sum rounds nothing; only the halves of an expert
        # larger than a whole chunk meet, in float32
        def more(start, grads):
            _, kept, stop = _chunk_fwd(x, w1, w3, w2, weight, order, sizes,
                                       chunk, start)
            part = _chunk_bwd(dout, kept, w1, w3, w2, inverse, k, chunk,
                              start)
            return stop, tuple(
                (a.astype(jnp.float32) + b.astype(jnp.float32))
                .astype(a.dtype) for a, b in zip(grads, part))

        stop = None if chunk is None else _span(sizes, 0, chunk)[0]
        dx, dw1, dw3, dw2, dweight = _later_chunks(
            chunk, order, sizes, stop, more,
            _chunk_bwd(dout, kept, w1, w3, w2, inverse, k, chunk))
    return dx.astype(x.dtype), dw1, dw3, dw2, dweight.reshape(-1, k), \
        None, None, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def sparse_moe(x, router_weight, w1, w3, w2, expert_bias, num_experts,
               num_experts_per_tok, first_expert=0, scoring="sigmoid",
               norm_topk_prob=True, routed_scaling_factor=1.0,
               norm_topk_eps=1e-6):
    """(the held experts' part of the layer's result, the k expert ids of
    every token as float32).  x [..., H]; router_weight [num_experts, H];
    w1, w3 [held, H, I] and w2 [held, I, H] for the experts
    ``first_expert .. first_expert + held - 1``; expert_bias
    [num_experts], added to the scores for the choice only;
    ``routed_scaling_factor`` (a family's ``route_scale``) multiplies the
    weights after their normalisation by the chosen scores' sum +
    ``norm_topk_eps``.

    Inside a recomputation segment (``ops/remat.py``) the routing's
    integers are kept for the backward: the expert ids, ``order``,
    ``inverse`` and ``sizes``, under 2 MB a layer at 131,072 routed rows,
    for which the replay would run ``top_k``'s selection and both
    ``argsort``s again.  The float side (logits, scores, weights) is
    replayed: the router's gradient needs it and it is one small product.
    The experts' rows are replayed too, by their bytes: what
    ``_chunk_fwd`` keeps (``xs``, ``h1``, ``h3``) is 65,536 x (2,048 + 2 x
    1,792) x 2 B = 738 MB a layer at LFM2's shapes, 2.95 GB over its four
    expert layers on a chip that stands at 94.7% of its memory."""
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
            bool(norm_topk_prob), float(routed_scaling_factor),
            float(norm_topk_eps))
        local = idx.reshape(-1) - int(first_expert)
        # rows of experts held elsewhere sort behind every group
        key = jnp.where((local >= 0) & (local < held), local, held)
        order = jnp.argsort(key, stable=True).astype(jnp.int32)
        inverse = jnp.argsort(order).astype(jnp.int32)
        # a compare and a sum: XLA:TPU's scatter-add (bincount) took 3.9 ms
        sizes = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                        dtype=jnp.int32)
        order, inverse, sizes = _remat.keep(order, inverse, sizes)
    _tel.bump("sparse_moe_traced")
    _tel.bump("sparse_moe_rows", rows)
    chunk = None
    if held < int(num_experts):
        chunk = _share_rows(rows, held, int(num_experts))
        _tel.bump("sparse_moe_held_rows_budget", chunk)
    out = _experts(flat, w1, w3, w2, weight, order, inverse, sizes, chunk)
    return out.reshape(x.shape), \
        idx.reshape(x.shape[:-1] + (k,)).astype(jnp.float32)


@register("_contrib_SparseMoE", aliases=["SparseMoE"], num_outputs=2,
          nondiff_inputs=(5,))
def _sparse_moe(data, router_weight, w1, w3, w2, expert_bias,
                num_experts=None, num_experts_per_tok=1, first_expert=0,
                scoring="sigmoid", norm_topk_prob=True,
                routed_scaling_factor=1.0, norm_topk_eps=1e-6, **kw):
    """Dropless sparse-expert SwiGLU layer: output 0 is the held experts'
    part of the result, output 1 the chosen expert ids [..., k] (float32,
    no gradient).  The stacked tensors' leading axis says how many experts
    are held, ``first_expert`` which; the router scores all
    ``num_experts``."""
    out, choice = sparse_moe(
        data, router_weight, w1, w3, w2, expert_bias, int(num_experts),
        int(num_experts_per_tok), int(first_expert), scoring,
        norm_topk_prob, routed_scaling_factor, norm_topk_eps)
    return out, jax.lax.stop_gradient(choice)
