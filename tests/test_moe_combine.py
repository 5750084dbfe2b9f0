"""The experts' combine as one Pallas pass (``ops/moe.py:_combine_pallas``)
in interpret mode, against the ``jnp`` form it replaces on a TPU: every
expert held at k = 4, 6 and 8; a partial share whose rows fall partly
outside the chunk, at the first chunk and at a later one; tokens none of
whose choices is held; the backward's two terms against their sum rounded
to bfloat16 and then combined; both output dtypes; and the gradient of the
whole layer at the widths of three models' layers, on the all-held path
and through the loop over later chunks.  The kernel adds a token's rows as
one-hot products on the MXU, in float32 in the order it staged them: the
sums agree with the gathers' to float32 rounding, and the rows they add
are the same.  The counter counts the call sites that take the kernel;
lowered for a TPU, the kernel appears once per shape, whatever the number
of layers and call sites."""
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import moe

COUNTER = "sparse_moe_combine_kernel_traced"
TOKENS, HIDDEN = 40, 256


def jnp_combine(y, inverse, k, chunk=None, start=0):
    """The combine as the layer computed it before the kernel: k gathers
    of [T, H] in float32, added in choice order."""
    where = inverse.reshape(-1, k)
    return sum(moe._from_chunk(y, where[:, j], chunk, start)
               .astype(jnp.float32) for j in range(k))


@pytest.fixture
def kernel_path(monkeypatch):
    """The combine on its TPU path, the kernel in interpret mode."""
    monkeypatch.setattr(moe, "_kernel_backend", lambda: True)
    monkeypatch.setattr(moe, "_combine_pallas", functools.partial(
        moe._combine_pallas, interpret=True))


def routing(k, experts, held, seed, tokens=TOKENS, skew=1.0):
    """(the inverse of the sorted order of a top-k routing, as
    ``sparse_moe`` builds it (experts held elsewhere sort last), the held
    experts' rows)."""
    rng = np.random.RandomState(seed)
    logits = rng.randn(tokens, experts) + skew * rng.randn(experts)
    idx = np.argsort(-logits, axis=1, kind="stable")[:, :k].reshape(-1)
    key = np.where(idx < held, idx, held)
    order = np.argsort(key, kind="stable")
    return (jnp.asarray(np.argsort(order).astype(np.int32)),
            jnp.asarray(np.bincount(key, minlength=held + 1)[:held]
                        .astype(np.int32)))


def rows(n, seed, terms=1, width=HIDDEN):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(n, width), jnp.bfloat16)
                 for _ in range(terms))


def close_in(a, b, dtype):
    """The same sums to *dtype*'s rounding: float32 sums of at most k rows
    taken in another order, at most one step of bfloat16 apart where they
    are rounded to it after."""
    a, b = np.asarray(a), np.asarray(b)
    assert a.dtype == b.dtype and a.shape == b.shape
    rtol = 1e-6 if jnp.dtype(dtype) == jnp.float32 else 2.0 ** -7
    np.testing.assert_allclose(a.astype(np.float32), b.astype(np.float32),
                               rtol=rtol,
                               atol=rtol * np.abs(b.astype(np.float32)).max())


ALL_HELD = [(4, 8, jnp.bfloat16), (6, 16, jnp.bfloat16),
            (8, 16, jnp.bfloat16), (6, 16, jnp.float32)]


@pytest.mark.parametrize("k,experts,dtype", ALL_HELD,
                         ids=["k4-bf16", "k6-bf16", "k8-bf16", "k6-f32"])
def test_all_held_matches_the_gathers(kernel_path, k, experts, dtype):
    inverse, sizes = routing(k, experts, experts, seed=k)
    (y,) = rows(TOKENS * k, seed=1)
    before = telemetry.counter(COUNTER)
    got = jax.jit(lambda y, inv: moe._combine((y,), inv, k, sizes,
                                              dtype))(y, inverse)
    assert telemetry.counter(COUNTER) - before == 1
    close_in(got, jnp_combine(y, inverse, k).astype(dtype), dtype)


# the chunks of a quarter share: its first, of 96 rows, and a later one of
# 96 that starts where a first chunk of half the held rows stopped, at an
# expert's end, and runs past the held rows
SHARES = ["first", "later"]


def in_chunk(sizes, start, chunk):
    """(the held experts' rows inside the chunk that starts at *start*,
    which rows of the chunk are theirs [chunk, 1]), as the layer hands
    them to the combine."""
    stop, inside = moe._span(sizes, start, chunk)
    return inside, (jnp.arange(chunk) < stop - start)[:, None]


def chunk_start(sizes, which):
    if which == "first":
        return 0
    return int(moe._span(sizes, 0, int(jnp.sum(sizes)) // 2)[0])


@pytest.mark.parametrize("which", SHARES)
@pytest.mark.parametrize("k", [4, 6, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_rows_outside_the_chunk_add_nothing(kernel_path, which, k, dtype):
    experts, held, chunk = 16, 4, 96
    inverse, sizes = routing(k, experts, held, seed=3)
    start = chunk_start(sizes, which)
    inside, live = in_chunk(sizes, start, chunk)
    (y,) = rows(chunk, seed=2)
    got = jax.jit(lambda y, inv: moe._combine(
        (y,), inv, k, inside, dtype, chunk, start, live))(y, inverse)
    want = jnp_combine(jnp.where(live, y, 0), inverse, k, chunk, start)
    where = inverse.reshape(-1, k) - start
    read = (where >= 0) & (where < int(jnp.sum(inside)))
    # a share: most of a token's choices are outside the chunk
    assert 0 < float(jnp.mean(read)) < 0.7
    close_in(got, want.astype(dtype), dtype)


def test_a_token_with_no_held_choice_gets_zeros(kernel_path):
    """Tokens all of whose k experts are held elsewhere add nothing: their
    rows of the sum are zeros, exactly."""
    k, experts, held, chunk = 8, 32, 4, 64
    inverse, sizes = routing(k, experts, held, seed=11, skew=0.0)
    limit = int(jnp.sum(sizes))
    assert limit < chunk
    none = ~(np.asarray(inverse).reshape(-1, k) < limit).any(axis=1)
    assert 0 < none.sum() < TOKENS
    live = (jnp.arange(chunk) < limit)[:, None]
    (y,) = rows(chunk, seed=12)
    got = jax.jit(lambda y, inv: moe._combine(
        (y,), inv, k, sizes, jnp.float32, chunk, 0, live))(y, inverse)
    assert not np.asarray(got)[none].any()
    close_in(got, jnp_combine(jnp.where(live, y, 0), inverse, k, chunk, 0),
             jnp.float32)


@pytest.mark.parametrize("which", ["all-held"] + SHARES)
def test_two_terms_are_rounded_before_they_are_combined(kernel_path, which):
    chunk = None if which == "all-held" else 96
    k, experts = (6, 16) if chunk is None else (8, 16)
    held = experts if chunk is None else 4
    inverse, sizes = routing(k, experts, held, seed=4)
    live, start = None, 0
    if chunk is not None:
        start = chunk_start(sizes, which)
        sizes, live = in_chunk(sizes, start, chunk)
    a, b = rows(chunk or TOKENS * k, seed=5, terms=2)
    dtype = jnp.bfloat16 if chunk is None else jnp.float32
    got = jax.jit(lambda a, b, inv: moe._combine(
        (a, b), inv, k, sizes, dtype, chunk, start, live))(a, b, inverse)
    with jax.disable_jit():   # the rounding as written, op by op
        dxs = (a.astype(jnp.float32) + b.astype(jnp.float32)) \
            .astype(jnp.bfloat16)
        if live is not None:
            dxs = jnp.where(live, dxs, 0)
        want = jnp_combine(dxs, inverse, k, chunk, start).astype(dtype)
    close_in(got, want, dtype)


@pytest.mark.parametrize("terms", [1, 2])
def test_rows_past_the_groups_are_never_read(kernel_path, terms):
    """What the grouped products leave past the chunk's last group (here
    NaN, also inside the slab that holds the last live row) adds nothing:
    the ``jnp`` form zeroes it, the kernel neither adds it nor lets it meet
    a zero of its one-hot product."""
    k, experts, held, chunk, start = 8, 16, 4, 96, 0
    inverse, sizes = routing(k, experts, held, seed=9)
    sizes = sizes.at[-1].add(-int(jnp.sum(sizes)) + 84)   # 84 live rows
    live = (jnp.arange(chunk) < 84)[:, None]
    ys = tuple(jnp.where(live, y, jnp.nan)
               for y in rows(chunk, seed=10, terms=terms))
    got = jax.jit(lambda inv, *ys: moe._combine(
        ys, inv, k, sizes, jnp.float32, chunk, start, live))(inverse, *ys)
    with jax.disable_jit():
        zeroed = [jnp.where(live, y, 0) for y in ys]
        y = zeroed[0] if terms == 1 else (
            zeroed[0].astype(jnp.float32) +
            zeroed[1].astype(jnp.float32)).astype(jnp.bfloat16)
        want = jnp_combine(y, inverse, k, chunk, start)
    assert not np.isnan(np.asarray(got)).any()
    close_in(got, want, jnp.float32)


@pytest.mark.parametrize("held", [16, 4], ids=["all-held", "share"])
def test_the_plan_stages_each_row_within_its_bound(held):
    """Each tile stages, for each group, the 8-row slabs from its first row
    of the group to its last, within ``_combine_slots`` (whole one-hot
    products of staged rows); each (token, choice) finds its row there,
    and a row past the groups is staged for nobody."""
    k, experts, tokens, tile = 6, 16, 64, 16
    inverse, sizes = routing(k, experts, held, seed=6, tokens=tokens)
    limit = int(jnp.sum(sizes))
    where = inverse.reshape(-1, k)
    groups = held + 1
    slots = moe._combine_slots(tile, k, groups)
    assert slots % (moe._COMBINE_ROWS // 8) == 0
    table, pos = moe._combine_plan(where, jnp.int32(limit), sizes, tile)
    assert table.shape == (tokens // tile, 1, 3 * groups + 1)
    assert pos.shape == (tokens, k)
    for i in range(tokens // tile):
        first, count, at = np.asarray(table[i, 0, :-1]).reshape(3, groups)
        assert count.sum() == table[i, 0, -1] <= slots
        assert np.array_equal(at, np.cumsum(count) - count)
        source = np.concatenate([f + np.arange(c)
                                 for f, c in zip(first, count)])
        mine = np.asarray(where[i * tile:(i + 1) * tile]).reshape(-1)
        staged = np.asarray(pos[i * tile:(i + 1) * tile]).reshape(-1)
        read = mine < limit
        assert np.array_equal(staged < 0, ~read)
        assert np.array_equal(source[staged[read] // 8] * 8 +
                              staged[read] % 8, mine[read])
        # the slabs are those that hold the tile's rows, a shared one twice
        assert set(source) == set(mine[read] // 8)


# (hidden, expert width, experts, held, k, scoring, act, route apart):
# the three families' layers at a width of 256, their k, gate and router
FAMILIES = {
    "lfm2": (256, 96, 8, 8, 4, "sigmoid", "silu", False),
    "trinity": (256, 64, 16, 4, 8, "sigmoid", "silu", False),
    "smallthinker": (256, 48, 8, 8, 6, "softmax", "relu", True),
}


def layer_case(name, seed=0):
    hidden, width, experts, held, k, scoring, act, apart = FAMILIES[name]
    rng = np.random.RandomState(seed)

    def bf16(*shape, scale=1.0):
        return jnp.asarray(rng.randn(*shape) * scale, jnp.bfloat16)
    args = (bf16(TOKENS, hidden), bf16(experts, hidden, scale=0.3),
            bf16(held, hidden, width, scale=0.1),
            bf16(held, hidden, width, scale=0.1),
            bf16(held, width, hidden, scale=0.1))
    route = bf16(TOKENS, hidden) if apart else None

    def loss(x, router, w1, w3, w2, dout):
        out = moe.sparse_moe(x, router, w1, w3, w2, None, experts, k,
                             scoring=scoring, route=route, act=act)[0]
        return jnp.sum(out.astype(jnp.float32) * dout)
    return loss, args + (jnp.asarray(rng.randn(TOKENS, hidden),
                                     jnp.float32),)


def layer_grads(name, monkeypatch, kernel):
    loss, args = layer_case(name)
    with monkeypatch.context() as patch:
        if kernel:
            patch.setattr(moe, "_kernel_backend", lambda: True)
            patch.setattr(moe, "_combine_pallas", functools.partial(
                moe._combine_pallas, interpret=True))
        before = telemetry.counter(COUNTER)
        with jax.disable_jit():
            value, grads = jax.value_and_grad(loss, (0, 1, 2, 3, 4))(*args)
        return value, grads, telemetry.counter(COUNTER) - before


@pytest.mark.parametrize("name", list(FAMILIES))
def test_the_layers_gradient_is_the_jnp_combines(name, monkeypatch):
    """The layer's value and its gradients (dx through the backward's
    combine, the router's through dweight) on the kernel path against the
    ``jnp`` path's."""
    # 40 tokens route 160-320 rows: the grouped products stay
    # ``ragged_dot`` (no whole tile of 512), the combine takes the kernel
    held, experts = FAMILIES[name][3], FAMILIES[name][2]
    if held < experts:
        # chunks of 48 of 320 rows: the loop over later chunks runs
        monkeypatch.setattr(moe, "_SHARE_ROOM", 0.6)
    value, grads, counted = layer_grads(name, monkeypatch, kernel=True)
    want_value, want, none = layer_grads(name, monkeypatch, kernel=False)
    # forward and backward once each, and again in the later chunks' loop
    assert counted == (2 if held == experts else 4) and none == 0
    close_in(value, want_value, jnp.float32)
    for got, ref in zip(grads, want):
        close_in(got, ref, got.dtype)


def test_a_width_in_no_whole_lane_tiles_keeps_the_gathers(kernel_path):
    k, experts = 4, 8
    inverse, sizes = routing(k, experts, experts, seed=7)
    (y,) = rows(TOKENS * k, seed=8, width=192)
    before = telemetry.counter(COUNTER)
    got = moe._combine((y,), inverse, k, sizes, jnp.bfloat16)
    assert telemetry.counter(COUNTER) == before
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp_combine(y, inverse, k)
                                    .astype(jnp.bfloat16)))
    assert moe._combine_tile(TOKENS, k, experts + 1, 1, 192) is None


# --- three stacked layers, traced and lowered for a TPU on the host -------

LAYERS, STACK_TOKENS, WIDTH = 3, 512, 128
# (experts, held, k): a quarter share (the chunked path, the loop over
# later chunks in the program) and every expert held
PATHS = {"chunked": (16, 4, 8), "all-held": (8, 8, 4)}


def stack_loss(experts, k, remat):
    """Sparse-expert layers of one shape on a residual stream, each under
    ``jax.checkpoint`` when *remat* (as the executor's segments run them):
    the forward's combines, their replay and the backward's."""
    def layer(x, w):
        return moe.sparse_moe(x, *w, None, experts, k)[0]

    def loss(x, ws):
        for w in ws:
            x = x + (jax.checkpoint(layer) if remat else layer)(x, w)
        return jnp.sum(x.astype(jnp.float32))
    return loss


def stack_args(experts, held, layers=LAYERS):
    rng = np.random.RandomState(0)

    def bf16(*shape):
        return jnp.asarray(rng.randn(*shape) * 0.1, jnp.bfloat16)
    return bf16(STACK_TOKENS, HIDDEN), [
        (bf16(experts, HIDDEN), bf16(held, HIDDEN, WIDTH),
         bf16(held, HIDDEN, WIDTH), bf16(held, WIDTH, HIDDEN))
        for _ in range(layers)]


def lowered_combines(path, remat, layers):
    """(the calls of the combine's function, the Mosaic kernels ``moe_combine``
    in the module) of the gradient of *layers* layers, lowered for a TPU."""
    experts, held, k = PATHS[path]
    x, ws = stack_args(experts, held, layers)
    text = jax.jit(jax.grad(stack_loss(experts, k, remat), (0, 1))) \
        .trace(x, ws).lower(lowering_platforms=("tpu",)).as_text()
    return (re.findall(r"call @(_combine_call\w*)\(", text),
            len(re.findall(r'kernel_name = "moe_combine"', text)))


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "segments"])
@pytest.mark.parametrize("path", list(PATHS))
def test_the_kernel_is_lowered_once_per_shape(monkeypatch, path, remat):
    """The gradient of two and of four layers of one shape, lowered for a
    TPU: the combine is called at every call site (the forward, the
    backward, the later chunks' loop bodies, in every layer), but its
    Mosaic kernel is in the module once for each of its two shapes (the
    forward's one term, the backward's two) whatever the number of layers.
    A call inside a ``while_loop`` body keeps the function the trace made
    while the program's own dead-code pass gives the calls outside it a
    pruned copy: on the chunked path each shape is lowered once outside
    the later chunks' loops and once inside them."""
    monkeypatch.setattr(moe, "_kernel_backend", lambda: True)
    calls, kernels = lowered_combines(path, remat, 2)
    more, same = lowered_combines(path, remat, 4)
    assert len(more) >= len(calls) + 4, (calls, more)
    assert kernels == same == len(set(calls)) == len(set(more)) == \
        {"chunked": 4, "all-held": 2}[path], (set(more), kernels, same)


def test_the_counter_counts_call_sites_and_nothing_off_a_tpu():
    """At trace time the counter moves once for each call of the combine
    that takes the kernel, however many of them share a lowering, and not
    at all on the CPU's path."""
    experts, held, k = PATHS["chunked"]
    x, ws = stack_args(experts, held)
    loss = stack_loss(experts, k, False)
    before = telemetry.counter(COUNTER)
    jax.eval_shape(jax.grad(loss, (0, 1)), x, ws)
    assert telemetry.counter(COUNTER) == before
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "_kernel_backend", lambda: True)
        jax.eval_shape(jax.grad(loss, (0, 1)), x, ws)
    # per layer: the first chunk's forward and backward, the forward's
    # and the backward's loop bodies over later chunks
    assert telemetry.counter(COUNTER) - before == 4 * LAYERS
