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
again there.  The combine reads only the rows inside the chunk (see
``_combine``).  With every expert held there is one chunk of ``tokens x
k`` rows, no mask and no loop.

The combine, each token's k rows of the last product added in float32,
is one Pallas pass on a TPU (``_combine_pallas``): a tile of 128 tokens
copies, for each group, the 8-row slabs of the sorted order from its first
row of the group to its last, and adds its rows as one-hot products on the
MXU, 256 staged rows at a time; it writes its sums once.  The ``jnp`` form
gathers k [tokens, H] copies, of every row in or out of the chunk, and
reads them back (and the backward first writes the [rows, H] sum of its
two transposed products, which the kernel adds as it stages them).  The
same rows, added in another order.  The kernel is called through a
``jax.jit`` with its tiling static, so a program traces and lowers it once
per shape, not once per call site.

The router may read another tensor than the experts do (a family that
routes from the attention's input), and the experts' gate is SiLU
(SwiGLU) or ReLU (ReGLU): one routing routine and one expert machine for
all of them, the activation a static argument of the hand-derived
backward.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


_DEPTH = 1024
_TILE_AREA = 1024 * 1280   # the largest [depth, width] tgmm tile that fits


def _depth(d, lanes):
    """The tile over a dimension *d* that the backward's grouped products
    walk in steps (a transposed stack's contraction, a stacked gradient's
    rows) beside an output tile *lanes* wide: all of *d* up to
    ``_DEPTH``; else the deepest multiple of 256 from 512 to 1280 that
    divides *d* and keeps depth x lanes within ``_TILE_AREA`` (``tgmm``'s
    float32 [1280, 1280] accumulator overflows the 16 MiB of scoped VMEM);
    else ``_DEPTH``.  The kernels pad a last tile that *d* does not fill:
    ``gmm`` masks both operands in every step over it, ``tgmm`` computes
    the padded rows and drops them.  A shallow tile costs steps: at 2,560
    on the chip ``tgmm`` took longer in tiles of 512 than in the padded
    1,024, and least time in 1,280 (PERF.md section 5)."""
    if d <= _DEPTH:
        depth = d
    else:
        depth = next((t for t in range(1280, 511, -256)
                      if d % t == 0 and t * lanes <= _TILE_AREA), _DEPTH)
    if depth != _DEPTH:
        _tel.bump("sparse_moe_exact_tiles_traced")
    return depth


def _grouped(rows, stack, sizes, live=None, transpose=False):
    """rows [R, a] times the group's matrix of *stack* [G, a, b] ([G, b, a]
    with *transpose*) -> [R, b].  Rows past the last group are in no
    product: *live* [R, 1] zeroes what the kernel leaves there."""
    if _pallas_grouped(rows.shape[0]):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
        a, b = rows.shape[1], stack.shape[1 if transpose else 2]
        # tiles (rows, contracted, out): the whole contraction in one tile
        # when the stack is read as stored, ``_depth``'s when transposed
        lanes = _half(b)
        tiling = (_TILE_ROWS, _depth(a, lanes) if transpose else a, lanes)
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
        lanes = _half(rhs.shape[1])
        tiling = (_TILE_ROWS, _depth(lhs.shape[1], lanes), lanes)
        with jax.enable_x64(False):
            return tgmm(lhs.T, rhs, sizes, dtype, tiling)
    dims = _DIMS((([0], [0]), ([], [])), [0], [])
    return jax.lax.ragged_dot_general(lhs, rhs, sizes, dims,
                                      preferred_element_type=dtype)


_ACTS = ("silu", "relu")


def _gate(h1, h3, act):
    """(act(h1) * h3, what the gate's derivative reads of h1) in float32:
    sigmoid(h1) for SiLU (SwiGLU), the mask h1 > 0 for ReLU (ReGLU)."""
    h1, h3 = h1.astype(jnp.float32), h3.astype(jnp.float32)
    if act == "relu":
        on = h1 > 0
        return jnp.where(on, h1, 0.0) * h3, on
    sig = jax.nn.sigmoid(h1)
    return h1 * sig * h3, sig


def _gate_grads(da, h1, h3, act, d, dt):
    """(dh1, dh3) in *dt* from the cotangent *da* of act(h1) * h3, with *d*
    what ``_gate`` returned beside it."""
    h1f, h3f = h1.astype(jnp.float32), h3.astype(jnp.float32)
    if act == "relu":
        return (jnp.where(d, da * h3f, 0.0).astype(dt),
                (da * jnp.where(d, h1f, 0.0)).astype(dt))
    return ((da * h3f * d * (1.0 + h1f * (1.0 - d))).astype(dt),
            (da * h1f * d).astype(dt))


_SHARE_ROOM = 1.5       # a chunk of a partial share: this x the expected rows


def _share_rows(rows, held, num_experts):
    """Rows of one chunk of the sorted order when *held* of *num_experts*
    experts are held: ``_SHARE_ROOM`` times the share uniform routing
    gives, in whole row tiles where the routed rows are, never more than
    all of them."""
    unit = _TILE_ROWS if rows % _TILE_ROWS == 0 else 1
    want = -(-int(_SHARE_ROOM * rows * held) // (num_experts * unit)) * unit
    return min(rows, max(want, unit))


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9))
def _experts(x, w1, w3, w2, weight, order, inverse, sizes, chunk, act):
    """sum over a token's k choices of weight * (act(h W1_e) * (h W3_e))
    W2_e, *act* ``silu`` or ``relu``.  x [T, H]; weight [T, k] float32;
    *order* [T k]: the flat (token, choice) pair at each row of the
    expert-sorted order, *inverse* its inverse; *sizes* [G]: rows of each
    held expert.  *chunk* is None when every expert is held.  Otherwise the
    rows past sum(sizes) belong to experts held elsewhere and add nothing,
    and the sorted order is worked through *chunk* rows at a time, as far
    as sum(sizes) reaches."""
    return _experts_fwd(x, w1, w3, w2, weight, order, inverse, sizes,
                        chunk, act)[0]


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


def _combine(ys, inverse, k, sizes, dtype, chunk=None, start=0, live=None):
    """sum over a token's k choices of its rows of y (the chunk of the
    sorted order that starts at *start*, whose groups have *sizes* rows),
    in float32, in *dtype*.  *ys* is y, or two [rows, H] terms whose sum
    rounded to their dtype is y (the backward's two transposed products),
    as the grouped products left them: *live* [rows, 1] marks the rows in
    a group, the others are no row of y.

    On a TPU, for bfloat16 rows in whole lane tiles, one Pallas pass
    (``_combine_pallas``): it reads the 8-row slabs that hold a tile's rows,
    a slab outside the chunk or past its groups not at all, and writes
    [T, H] once.  Elsewhere (the CPU, odd widths or rows) ``jnp``: the rows
    past the groups zeroed, two terms added into [rows, H], then k gathers
    of [T, H], each written and read again by their sum: the bytes of a
    [T, k, H] tensor."""
    where = inverse.reshape(-1, k)
    rows = ys[0].shape[0]
    groups = sizes.shape[0] + 1     # the held experts' and the rest
    tile = _combine_tile(where.shape[0], k, groups, len(ys), ys[0].shape[1])
    if _kernel_backend() and tile and rows % 8 == 0 and \
            all(y.dtype == jnp.bfloat16 for y in ys):
        _tel.bump("sparse_moe_combine_kernel_traced")
        return _combine_pallas(
            tuple(ys), inverse, jnp.asarray(start, jnp.int32),
            jnp.asarray(rows if live is None else jnp.sum(sizes), jnp.int32),
            sizes.astype(jnp.int32), k=k, tile=tile,
            slots=_combine_slots(tile, k, groups), dtype=jnp.dtype(dtype))
    if live is not None:
        ys = [jnp.where(live, y, 0) for y in ys]
    y = ys[0] if len(ys) == 1 else (ys[0].astype(jnp.float32) +
                                    ys[1].astype(jnp.float32)) \
        .astype(ys[0].dtype)
    return sum(_from_chunk(y, where[:, j], chunk, start).astype(jnp.float32)
               for j in range(k)).astype(dtype)


_COMBINE_STAGE_BYTES = 64 << 20   # both slots' slabs of every term
_COMBINE_VMEM_BYTES = 100 << 20
_COMBINE_ROWS = 256               # staged rows a one-hot product reads


def _combine_slots(tile, k, groups):
    """Slabs of 8 sorted rows that a tile of *tile* tokens reads at most,
    in whole products of ``_COMBINE_ROWS`` rows: the rows of one group a
    tile takes are contiguous in the sorted order (it is stable by token),
    so each group's run lies in its rows / 8 + 2 slabs."""
    per = _COMBINE_ROWS // 8
    return -(-min(tile * k, -(-tile * k // 8) + 2 * groups) // per) * per


def _combine_tile(tokens, k, groups, terms, width):
    """The largest tile of tokens (8 to 128) that divides *tokens* and
    whose slabs, double-buffered for every term, fit
    ``_COMBINE_STAGE_BYTES``; None if width is no whole lane tiles."""
    if width % 128:
        return None
    return next((t for t in (128, 64, 32, 16, 8)
                 if tokens % t == 0 and 2 * terms * _combine_slots(
                     t, k, groups) * 8 * width * 2 <= _COMBINE_STAGE_BYTES),
                None)


def _combine_plan(where, limit, sizes, tile):
    """(what each tile stages [tiles, 1, 3 (G + 1) + 1]: for each group of
    the chunk, and for the rows past them, its first slab of 8 sorted rows,
    how many slabs from there and where they go among the staged ones,
    then their count; each (token, choice)'s row among its tile's staged
    slabs [T, k], -1 for a row outside 0 .. *limit* - 1), int32.  *sizes*
    [G] are the groups' rows in the chunk, in order from its row 0.

    The rows of one group that a tile of tokens takes are contiguous in
    the sorted order (it is stable by token), so a tile reads, for each
    group, the slabs from its first row there to its last: no sort, a slab
    that two groups share read once for each."""
    tokens, k = where.shape
    tiles, groups = tokens // tile, sizes.shape[0] + 1
    p = where.reshape(tiles, tile * k)
    live = (p >= 0) & (p < limit)
    ends = jnp.cumsum(sizes.astype(jnp.int32))
    group = jnp.sum(p[..., None] >= ends, axis=-1, dtype=jnp.int32)
    mine = live[..., None] & (group[..., None] == jnp.arange(groups))
    big = jnp.iinfo(jnp.int32).max
    low = jnp.min(jnp.where(mine, p[..., None], big), axis=1)
    high = jnp.max(jnp.where(mine, p[..., None], -1), axis=1)
    first = jnp.where(high >= low, low // 8, 0)
    count = jnp.where(high >= low, high // 8 + 1 - first, 0)
    at = jnp.cumsum(count, axis=1, dtype=jnp.int32) - count
    table = jnp.concatenate([first, count, at, at[:, -1:] + count[:, -1:]],
                            axis=1).astype(jnp.int32)
    shift = jnp.sum(jnp.where(mine, (8 * (at - first))[:, None, :], 0),
                    axis=-1, dtype=jnp.int32)
    pos = jnp.where(live, p + shift, -1)
    return table[:, None, :], pos.astype(jnp.int32).reshape(tokens, k)


def _loop(n, body):
    """``body(i)`` for int32 i in 0 .. n - 1 (a traced n); under x64
    ``fori_loop``'s own counter is 64-bit, which Mosaic has not."""
    def step(i):
        body(i)
        return jax.lax.add(i, np.int32(1))
    jax.lax.while_loop(lambda i: i < n, step, np.int32(0))


def _combine_kernel(table_ref, after_ref, pos_ref, *refs, k, groups,
                    terms):
    """One tile of tokens: start the next tile's slab copies, wait for this
    tile's (started a step before), then add each token's k rows in
    float32 as one-hot products on the MXU, ``_COMBINE_ROWS`` staged rows
    at a time, and write the tile once."""
    srcs, (out_ref, acc_ref, sem), stages = \
        refs[:terms], refs[terms:terms + 3], refs[terms + 3:]
    i, tiles = pl.program_id(0), pl.num_programs(0)

    def copies(slot, m, c):
        return [pltpu.make_async_copy(src.at[pl.ds(c, 1)],
                                      stage.at[slot, pl.ds(m, 1)],
                                      sem.at[slot])
                for src, stage in zip(srcs, stages)]

    def fetch(table, slot):
        def group(g):
            first, at = table[0, g], table[0, 2 * groups + g]

            def one(r):
                for copy in copies(slot, at + r, first + r):
                    copy.start()
            _loop(table[0, groups + g], one)
        _loop(np.int32(groups), group)

    @pl.when(i == 0)
    def _first():
        fetch(table_ref, np.int32(0))

    @pl.when(i + 1 < tiles)
    def _next():
        fetch(after_ref, jax.lax.rem(i + 1, np.int32(2)))

    slot = jax.lax.rem(i, np.int32(2))
    staged = table_ref[0, 3 * groups]

    def wait(m):
        for copy in copies(slot, np.int32(0), np.int32(0)):
            copy.wait()
    _loop(staged, wait)

    f32, n, per = jnp.float32, _COMBINE_ROWS, _COMBINE_ROWS // 8
    pos = pos_ref[...]
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def product(c):
        # the tile's one-hot [tile, n] over staged rows c n .. c n + n - 1
        # (-1, a row outside the chunk, hits none): row t adds exactly
        # its own rows, in float32
        base = jax.lax.mul(c, np.int32(n))
        col = jax.lax.broadcasted_iota(jnp.int32, (pos.shape[0], n), 1) + base
        hit = pos[:, 0:1] == col
        for j in range(1, k):
            hit = hit | (pos[:, j:j + 1] == col)
        at = pl.ds(pl.multiple_of(jax.lax.mul(c, np.int32(per)), per), per)
        y = [stage[slot, at].reshape(n, stage.shape[-1]) for stage in stages]
        y = y[0] if terms == 1 else \
            (y[0].astype(f32) + y[1].astype(f32)).astype(jnp.bfloat16)
        # staged rows past this tile's slabs hold an earlier tile's rows or
        # nothing yet: zeros, so that no NaN meets a zero of the one-hot
        row = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) + base
        y = jnp.where(row < jax.lax.mul(staged, np.int32(8)), y,
                      jnp.zeros_like(y))
        acc_ref[...] += jnp.dot(hit.astype(jnp.bfloat16), y,
                                preferred_element_type=f32)
    _loop(jax.lax.div(staged + np.int32(per - 1), np.int32(per)), product)
    out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def _combine_call(ys, inverse, start, limit, sizes, *, k, tile, slots,
                  dtype, interpret=False):
    """``_combine``'s Pallas pass: [T, H] in *dtype* from the [rows, H]
    bfloat16 terms *ys* at the positions *inverse* [T k] - *start*, a row
    outside 0 .. *limit* - 1 adding nothing; the chunk's groups have
    *sizes* rows.

    Called as ``_combine_pallas``, a ``jax.jit`` with the tiling static,
    as JAX's ``gmm`` is: every call of one shape in a program (the layers,
    the forward, its replay and the backward, the later chunks' loops)
    shares one traced jaxpr and one lowered function, so tracing and
    lowering the kernel is paid once per shape, not once per call."""
    where = inverse.reshape(-1, k) - start
    tokens = where.shape[0]
    rows, width = ys[0].shape
    tiles, groups = tokens // tile, sizes.shape[0] + 1
    table, pos = _combine_plan(where, limit, sizes, tile)
    # the slab that holds the groups' last row holds rows past them too,
    # which the grouped products left unwritten: zeros (in place; nothing
    # when the groups fill the rows)
    at = jnp.minimum(limit // 8 * 8, rows - 8)
    keep = (jnp.arange(8) + at < limit)[:, None]
    ys = tuple(jax.lax.dynamic_update_slice_in_dim(
        y, jnp.where(keep, jax.lax.dynamic_slice_in_dim(y, at, 8), 0),
        at, 0) for y in ys)
    last = np.int32(tiles - 1)
    entries = 3 * groups + 1
    smem = functools.partial(pl.BlockSpec, memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_combine_kernel, k=k, groups=groups,
                          terms=len(ys)),
        grid=(tiles,),
        in_specs=[smem((None, 1, entries),
                       lambda i: (i, np.int32(0), np.int32(0))),
                  smem((None, 1, entries), lambda i: (
                      jnp.minimum(i + np.int32(1), last), np.int32(0),
                      np.int32(0))),
                  pl.BlockSpec((tile, k), lambda i: (i, np.int32(0)))] +
        [pl.BlockSpec(memory_space=pl.ANY)] * len(ys),
        out_specs=pl.BlockSpec((tile, width), lambda i: (i, np.int32(0))),
        out_shape=jax.ShapeDtypeStruct((tokens, width), dtype),
        scratch_shapes=[pltpu.VMEM((tile, width), jnp.float32),
                        pltpu.SemaphoreType.DMA((2,))] +
        [pltpu.VMEM((2, slots, 8, width), jnp.bfloat16)] * len(ys),
        name="moe_combine", interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_COMBINE_VMEM_BYTES),
    )(table, table, pos, *[y.reshape(rows // 8, 8, width) for y in ys])


_combine_pallas = _tel.watch_jit(jax.jit(_combine_call, static_argnames=(
    "k", "tile", "slots", "dtype", "interpret")), "moe_combine")


def _chunk_fwd(x, w1, w3, w2, weight, order, sizes, chunk, act, start=0):
    """(the chunk's rows of the last product, what its backward reads,
    where the chunk stops)."""
    rows, stop = _sorted_rows(x, weight, order, sizes, chunk, start)
    xs, ws, live, sizes, _ = rows
    h1 = _grouped(xs, w1, sizes, live)
    h3 = _grouped(xs, w3, sizes, live)
    # the combine's weight goes in before the last product, which is
    # linear in its rows: the backward then needs no copy of its output
    aw = (_gate(h1, h3, act)[0] * ws[:, None]).astype(x.dtype)
    # rows past the groups are zeroed where the combine reads them
    return _grouped(aw, w2, sizes), rows + (h1, h3), stop


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


def _sum_dtype(x, order, chunk):
    """The combine's dtype: float32 where later chunks add to it, else
    x's, which the float32 sum is rounded to once."""
    return jnp.float32 if chunk is not None and chunk < order.shape[0] \
        else x.dtype


def _experts_fwd(x, w1, w3, w2, weight, order, inverse, sizes, chunk, act):
    k, dtype = weight.shape[1], _sum_dtype(x, order, chunk)

    def combine(y, kept, start=0):
        live, sizes = kept[2:4]
        return _combine((y,), inverse, k, sizes, dtype, chunk, start, live)

    with jax.named_scope("moe_experts_fwd"):
        y, kept, stop = _chunk_fwd(x, w1, w3, w2, weight, order, sizes,
                                   chunk, act)

        def more(start, out):
            y, kept, stop = _chunk_fwd(x, w1, w3, w2, weight, order, sizes,
                                       chunk, act, start)
            return stop, out + combine(y, kept, start)

        out = _later_chunks(chunk, order, sizes, stop, more,
                            combine(y, kept))
    return out.astype(x.dtype), (x, weight, kept, w1, w3, w2, order, inverse,
                                 sizes)


def _chunk_bwd(dout, kept, w1, w3, w2, inverse, k, chunk, act, dtype,
               start=0):
    """One chunk's part of (dx [T, H] in *dtype*, dw1, dw3, dw2, dweight
    [T k] float32) from what ``_chunk_fwd`` kept."""
    xs, ws, live, sizes, head, h1, h3 = kept
    dt = xs.dtype
    g = _rows(dout.astype(dt), head // k)
    a, d = _gate(h1, h3, act)
    daw = _grouped(g, w2, sizes, live, transpose=True).astype(jnp.float32)
    dws = jnp.sum(daw * a, axis=-1)
    da = daw * ws[:, None]
    dh1, dh3 = _gate_grads(da, h1, h3, act, d, dt)
    # dx's rows are the sum of the two transposed products rounded to dt:
    # the combine adds them as it reads them
    dxs = (_grouped(dh1, w1, sizes, transpose=True),
           _grouped(dh3, w3, sizes, transpose=True))
    dw1 = _grouped_outer(xs, dh1, sizes, w1.dtype)
    dw3 = _grouped_outer(xs, dh3, sizes, w3.dtype)
    dw2 = _grouped_outer((a * ws[:, None]).astype(dt), g, sizes, w2.dtype)
    return (_combine(dxs, inverse, k, sizes, dtype, chunk, start, live),
            dw1, dw3, dw2, _from_chunk(dws, inverse, chunk, start))


def _experts_bwd(chunk, act, res, dout):
    x, weight, kept, w1, w3, w2, order, inverse, sizes = res
    k, dtype = weight.shape[1], _sum_dtype(x, order, chunk)
    with jax.named_scope("moe_experts_bwd"):
        # a later chunk's activations were not kept and are computed
        # again.  An expert's rows are in one chunk (``_span``), so of the
        # two parts of a stack's gradient added here one is zero for every
        # expert and the sum rounds nothing; only the halves of an expert
        # larger than a whole chunk meet, in float32
        def more(start, grads):
            _, kept, stop = _chunk_fwd(x, w1, w3, w2, weight, order, sizes,
                                       chunk, act, start)
            part = _chunk_bwd(dout, kept, w1, w3, w2, inverse, k, chunk,
                              act, dtype, start)
            return stop, tuple(
                (a.astype(jnp.float32) + b.astype(jnp.float32))
                .astype(a.dtype) for a, b in zip(grads, part))

        stop = None if chunk is None else _span(sizes, 0, chunk)[0]
        dx, dw1, dw3, dw2, dweight = _later_chunks(
            chunk, order, sizes, stop, more,
            _chunk_bwd(dout, kept, w1, w3, w2, inverse, k, chunk, act,
                       dtype))
    return dx.astype(x.dtype), dw1, dw3, dw2, dweight.reshape(-1, k), \
        None, None, None


_experts.defvjp(_experts_fwd, _experts_bwd)


def sparse_moe(x, router_weight, w1, w3, w2, expert_bias, num_experts,
               num_experts_per_tok, first_expert=0, scoring="sigmoid",
               norm_topk_prob=True, routed_scaling_factor=1.0,
               norm_topk_eps=1e-6, route=None, act="silu"):
    """(the held experts' part of the layer's result, the k expert ids of
    every token as float32).  x [..., H]; router_weight [num_experts, R];
    w1, w3 [held, H, I] and w2 [held, I, H] for the experts
    ``first_expert .. first_expert + held - 1``; expert_bias
    [num_experts] or None, added to the scores for the choice only;
    ``routed_scaling_factor`` (a family's ``route_scale``) multiplies the
    weights after their normalisation by the chosen scores' sum +
    ``norm_topk_eps``.  The router reads *route* [..., R] where one is
    given (its gradient goes there), else x; the experts read x and gate
    by *act*: ``silu`` (SwiGLU) or ``relu`` (ReGLU).

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
    if act not in _ACTS:
        raise ValueError("sparse_moe: act %r (%s)" % (act, ", ".join(_ACTS)))
    flat = x.reshape(-1, x.shape[-1])
    rows = flat.shape[0] * k
    if route is None:
        src = flat
    elif route.shape[:-1] != x.shape[:-1]:
        raise ValueError("sparse_moe: a routing input of %s for data of %s"
                         % (route.shape, x.shape))
    else:
        src = route.reshape(-1, route.shape[-1])
        _tel.bump("sparse_moe_router_apart_traced")
    with jax.named_scope("moe_route"):
        logits = jnp.dot(src.astype(jnp.float32),
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
            scores, k, None if expert_bias is None else
            jax.lax.stop_gradient(expert_bias.astype(jnp.float32)),
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
    if act == "relu":
        _tel.bump("sparse_moe_reglu_traced")
    chunk = None
    if held < int(num_experts):
        chunk = _share_rows(rows, held, int(num_experts))
        _tel.bump("sparse_moe_held_rows_budget", chunk)
    out = _experts(flat, w1, w3, w2, weight, order, inverse, sizes, chunk,
                   act)
    return out.reshape(x.shape), \
        idx.reshape(x.shape[:-1] + (k,)).astype(jnp.float32)


# the selection bias has no gradient by stop_gradient inside the op, not
# by position: with ``route_apart`` input 5 is the routing operand
@register("_contrib_SparseMoE", aliases=["SparseMoE"], num_outputs=2)
def _sparse_moe(data, router_weight, w1, w3, w2, *rest, num_experts=None,
                num_experts_per_tok=1, first_expert=0, scoring="sigmoid",
                norm_topk_prob=True, routed_scaling_factor=1.0,
                norm_topk_eps=1e-6, act_type="silu", route_apart=False,
                no_bias=False, **kw):
    """Dropless sparse-expert layer, SwiGLU (``act_type="silu"``) or ReGLU
    (``"relu"``): output 0 is the held experts' part of the result, output
    1 the chosen expert ids [..., k] (float32, no gradient).  The stacked
    tensors' leading axis says how many experts are held, ``first_expert``
    which; the router scores all ``num_experts``.  After the three stacks
    come the routing operand ``route_data`` if ``route_apart`` (the router
    reads it instead of ``data``) and the auxiliary ``expert_bias`` unless
    ``no_bias``."""
    rest = list(rest)
    route = rest.pop(0) if route_apart else None
    expert_bias = None if no_bias else rest.pop(0)
    out, choice = sparse_moe(
        data, router_weight, w1, w3, w2, expert_bias, int(num_experts),
        int(num_experts_per_tok), int(first_expert), scoring,
        norm_topk_prob, routed_scaling_factor, norm_topk_eps, route,
        act_type)
    return out, jax.lax.stop_gradient(choice)
