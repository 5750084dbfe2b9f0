"""How the sparse-expert layer tiles its grouped products on a TPU, read
on the CPU: the tilings that ``_grouped`` and ``_grouped_outer`` hand
JAX's Pallas ``gmm``/``tgmm`` at three models' widths (the kernels
replaced by recorders, the layer traced for shapes only), the counter of
tiles the shape rule set, and the kernels themselves in interpret mode at
a rule-chosen depth against ``jax.lax.ragged_dot``."""
import functools
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops import moe

# the module (the package's own name ``gmm`` is the function)
megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

COUNTER = "sparse_moe_exact_tiles_traced"
TOKENS, EXPERTS, K = 512, 4, 4          # 2,048 routed rows: whole row tiles

# (hidden, expert width): LFM2-8B-A1B, Trinity-Mini, SmallThinker-21BA3B
WIDTHS = {"lfm2": (2048, 1792), "trinity": (2048, 1024),
          "smallthinker": (2560, 768)}

# the tilings (rows, depth, width) every product had before the depth was
# chosen by shape: LFM2's and Trinity's programs must not change
PINNED = {
    "lfm2": {"h1": (512, 2048, 896), "h3": (512, 2048, 896),
             "out": (512, 1792, 1024), "daw": (512, 1024, 896),
             "dx1": (512, 1024, 1024), "dx3": (512, 1024, 1024),
             "dw1": (512, 1024, 896), "dw3": (512, 1024, 896),
             "dw2": (512, 1024, 1024)},
    "trinity": {"h1": (512, 2048, 512), "h3": (512, 2048, 512),
                "out": (512, 1024, 1024), "daw": (512, 1024, 512),
                "dx1": (512, 1024, 1024), "dx3": (512, 1024, 1024),
                "dw1": (512, 1024, 512), "dw3": (512, 1024, 512),
                "dw2": (512, 1024, 1024)},
}

# the layer's products in the order it traces them, each with the
# dimension its depth tiles (None: the forward's, the whole contraction)
# and its operands' shapes as functions of (hidden, width)
PRODUCTS = {
    "h1": (None, lambda h, i: ((h,), (EXPERTS, h, i))),
    "h3": (None, lambda h, i: ((h,), (EXPERTS, h, i))),
    "out": (None, lambda h, i: ((i,), (EXPERTS, i, h))),
    "daw": ("hidden", lambda h, i: ((h,), (EXPERTS, i, h))),
    "dx1": ("width", lambda h, i: ((i,), (EXPERTS, h, i))),
    "dx3": ("width", lambda h, i: ((i,), (EXPERTS, h, i))),
    "dw1": ("hidden", lambda h, i: ((h,), (i,))),
    "dw3": ("hidden", lambda h, i: ((h,), (i,))),
    "dw2": ("width", lambda h, i: ((i,), (h,))),
}


@functools.lru_cache(maxsize=None)
def traced_tilings(model):
    """{product: (tiling, operand shapes, counter bumps before its call)}
    from one trace of the layer's forward and backward on the kernel path,
    every expert held, the kernels replaced by recorders."""
    hidden, width = WIDTHS[model]
    calls = []
    last = [telemetry.counter(COUNTER)]

    def record(kind, a, b, tiling, **kw):
        now = telemetry.counter(COUNTER)
        # a: [R, a] rows; b: the stack [G, ., .] or the other rows [R, n]
        calls.append((kind, kw.get("transpose_rhs", False), tiling,
                      (a.shape[1:], b.shape if kind == "gmm" else
                       b.shape[1:]), now - last[0]))
        last[0] = now

    def gmm(lhs, rhs, sizes, dtype, tiling, transpose_rhs=False):
        record("gmm", lhs, rhs, tiling, transpose_rhs=transpose_rhs)
        n = rhs.shape[1 if transpose_rhs else 2]
        return jnp.zeros((lhs.shape[0], n), dtype)

    def tgmm(lhs, rhs, sizes, dtype, tiling):
        # lhs arrives as [a, R]: report it as the [R, a] it was made from
        record("tgmm", lhs.T, rhs, tiling)
        return jnp.zeros((sizes.shape[0], lhs.shape[0], rhs.shape[1]), dtype)

    patch = pytest.MonkeyPatch()
    patch.setattr(moe, "_kernel_backend", lambda: True)
    patch.setattr(megablox, "gmm", gmm)
    patch.setattr(megablox, "tgmm", tgmm)
    try:
        def loss(x, router, w1, w3, w2):
            return moe.sparse_moe(x, router, w1, w3, w2, None, EXPERTS, K)[0] \
                .astype(jnp.float32).sum()
        spec = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
        jax.eval_shape(jax.grad(loss, (0, 1, 2, 3, 4)),
                       spec((TOKENS, hidden)), spec((EXPERTS, hidden)),
                       spec((EXPERTS, hidden, width)),
                       spec((EXPERTS, hidden, width)),
                       spec((EXPERTS, width, hidden)))
    finally:
        patch.undo()
    assert len(calls) == len(PRODUCTS), calls
    return dict(zip(PRODUCTS, calls))


CASES = [(m, p) for m in WIDTHS for p in PRODUCTS]


@pytest.mark.parametrize("model,product", CASES,
                         ids=["%s-%s" % c for c in CASES])
def test_each_grouped_product_gets_its_tile(model, product):
    hidden, width = WIDTHS[model]
    kind, transpose, tiling, shapes, bumped = traced_tilings(model)[product]
    tiled, operands = PRODUCTS[product]
    # the call is the product the name says
    assert (kind == "tgmm") == product.startswith("dw")
    assert transpose == (product in ("daw", "dx1", "dx3"))
    assert shapes == operands(hidden, width)
    rows, depth, lanes = tiling
    assert rows == moe._TILE_ROWS
    if model in PINNED:
        assert tiling == PINNED[model][product]
    if tiled is None:
        # the forward reads the stack as stored: the whole contraction
        assert depth == shapes[0][0] and bumped == 0
        return
    dim = hidden if tiled == "hidden" else width
    if model == "smallthinker":
        assert dim % depth == 0 and depth >= 512
        # the deepest that divides: the least time on the chip (PERF.md)
        assert depth == {2560: 1280, 768: 768}[dim]
    # the counter moved for this product exactly when its tile is not the
    # fixed 1,024 the backward took at every width before
    assert bumped == int(depth != 1024)


def test_the_rule_by_dimension():
    # (dimension, output tile's width) -> depth: all of a dimension up to
    # 1,024; then the deepest of 1,280, 1,024, 768, 512 that divides it
    # with depth x width within 1,024 x 1,280; else 1,024 over a padded
    # last tile
    for d, lanes, want in ((384, 384, 384), (640, 384, 640),
                           (768, 1280, 768), (1024, 1280, 1024),
                           (1536, 384, 768), (1792, 896, 1024),
                           (1792, 1024, 1024), (2048, 896, 1024),
                           (2560, 384, 1280), (2560, 1024, 1280),
                           (2560, 1280, 512), (3072, 384, 1024),
                           (3584, 384, 512), (5120, 384, 1280)):
        assert moe._depth(d, lanes) == want, (d, lanes)


def test_the_counter_reads_six_a_smallthinker_layer_and_none_elsewhere():
    bumps = {m: sum(c[-1] for c in traced_tilings(m).values())
             for m in WIDTHS}
    assert bumps == {"lfm2": 0, "trinity": 0, "smallthinker": 6}


def test_off_a_tpu_no_tile_is_chosen():
    """ragged_dot takes no tile: nothing is counted on the CPU path."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(64, 24), jnp.float32)
    router = jnp.asarray(rng.randn(4, 24), jnp.float32)
    w1, w3 = (jnp.asarray(rng.randn(4, 24, 16) * 0.3, jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(rng.randn(4, 16, 24) * 0.3, jnp.float32)
    before = telemetry.counter(COUNTER)
    jax.eval_shape(jax.grad(lambda *a: moe.sparse_moe(*a, None, 4, 2)[0]
                            .sum(), (0, 2, 3, 4)), x, router, w1, w3, w2)
    assert telemetry.counter(COUNTER) == before


# --- the kernels at a rule-chosen depth, in interpret mode ------------------

SIZES = np.array([300, 0, 212, 512], np.int32)   # uneven, one group empty


def operands(k, n, seed):
    rng = np.random.RandomState(seed)
    rows = jnp.asarray(rng.randn(int(SIZES.sum()), k), jnp.float32)
    stack = jnp.asarray(rng.randn(len(SIZES), n, k) * 0.1, jnp.float32)
    other = jnp.asarray(rng.randn(int(SIZES.sum()), n), jnp.float32)
    return rows, stack, other, jnp.asarray(SIZES)


@pytest.mark.parametrize("k,n", [(640, 384), (1536, 384), (2560, 384)])
def test_transposed_gmm_at_the_chosen_depth_is_ragged_dot(k, n):
    rows, stack, _, sizes = operands(k, n, 1)
    lanes = moe._half(n)
    tiling = (moe._TILE_ROWS, moe._depth(k, lanes), lanes)
    assert k % tiling[1] == 0
    with jax.enable_x64(False):
        got = megablox.gmm(rows, stack, sizes, jnp.float32, tiling,
                           transpose_rhs=True, interpret=True)
    want = jax.lax.ragged_dot(rows, jnp.swapaxes(stack, 1, 2), sizes,
                              precision=jax.lax.Precision.HIGHEST)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("k,n", [(640, 384), (1536, 384), (2560, 384)])
def test_tgmm_at_the_chosen_depth_is_ragged_dot_general(k, n):
    rows, _, other, sizes = operands(k, n, 2)
    lanes = moe._half(n)
    tiling = (moe._TILE_ROWS, moe._depth(k, lanes), lanes)
    assert k % tiling[1] == 0
    with jax.enable_x64(False):
        got = megablox.tgmm(rows.T, other, sizes, jnp.float32, tiling,
                            interpret=True)
    dims = jax.lax.RaggedDotDimensionNumbers((([0], [0]), ([], [])), [0], [])
    want = jax.lax.ragged_dot_general(rows, other, sizes, dims,
                                      precision=jax.lax.Precision.HIGHEST)
    assert got.shape == (len(SIZES), k, n)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)
    assert not np.asarray(got[1]).any()              # the empty group
