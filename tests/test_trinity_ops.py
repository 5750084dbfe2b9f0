"""What the AFMoE (Trinity) model asks of the ops, on the CPU at small
sizes, seeded, float32: a window in the flash kernels (interpret mode)
and in ``_contrib_CausalAttention``, the sparse-expert op's bounded
partial share, ``route_scale`` and the normalisation's epsilon, and the
layer kinds of ``afmoe_symbol``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.trinity import AFMOE_TINY, afmoe_symbol
from mxnet_tpu.ops import lm, moe
from mxnet_tpu.ops import pallas_kernels as pk

TILE = 128


def qkv(s, hq, hkv, d, seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(*shape), jnp.float32)
            for shape in ((1, hq, s, d), (1, hkv, s, d), (1, hkv, s, d),
                          (1, hq, s, d))]


def kernel(window, tile=TILE, scale=None):
    return lambda q, k, v: pk.flash_attention(q, k, v, True, scale, tile,
                                              tile, True, window)


# windows shorter than a tile, not a multiple of it, equal to S and longer
# than S; S not a multiple of the tile; 8/1 and 4/4 heads; sizes 64 and 128
@pytest.mark.parametrize("s,hq,hkv,d,window", [
    (300, 8, 1, 64, 50), (300, 4, 4, 128, 200), (256, 4, 4, 64, 256),
    (256, 8, 1, 128, 1000), (300, 8, 1, 64, 129), (100, 4, 4, 64, 7),
    (384, 4, 4, 64, 128), (300, 4, 4, 64, 1)])
def test_windowed_kernels_equal_the_masked_softmax(s, hq, hkv, d, window):
    q, k, v, do = qkv(s, hq, hkv, d)
    scale = d ** -0.5
    got, vjp = jax.vjp(kernel(window, scale=scale), q, k, v)
    want, ref_vjp = jax.vjp(lambda *x: lm._masked_softmax_attention(
        *x, scale, True, window), q, k, v)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    for name, a, b in zip("qkv", vjp(do), ref_vjp(do)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=5e-5, err_msg=name)


def test_banded_grids_are_as_long_as_the_band():
    """ceil((window - 1) / tile) + 1 tiles on the innermost axis of all
    three kernels, whatever the sequence's length; the whole triangle
    without a window."""
    q, k, v, do = qkv(1024, 2, 1, 64)

    def grids(window):
        text = str(jax.make_jaxpr(lambda *x: jax.vjp(
            kernel(window), *x[:3])[1](x[3]))(q, k, v, do))
        return sorted(set(
            line.split("grid=")[1].split(")")[0] + ")"
            for line in text.splitlines() if "grid=(" in line))

    assert grids(None) == ["(1, 8, 2, 8)", "(2, 8, 8)"]
    assert grids(200) == ["(1, 8, 2, 3)", "(2, 8, 3)"]      # 2 + 1
    assert grids(128) == ["(1, 8, 2, 2)", "(2, 8, 2)"]
    assert grids(129) == ["(1, 8, 2, 2)", "(2, 8, 2)"]
    assert grids(130) == ["(1, 8, 2, 3)", "(2, 8, 3)"]
    assert grids(5000) == grids(None)
    assert pk._band_len(512, 512, 2048, 32) == 5
    assert pk._band_len(512, 512, 2048, 3) == 3
    # unlike tiles: never shorter than the widest band any outer tile has
    for bq, bk, window in ((48, 128, 200), (128, 384, 130), (256, 128, 1)):
        seen = max(((qi + 1) * bq - 1) // bk -
                   max(qi * bq - (window - 1), 0) // bk + 1
                   for qi in range(64))
        assert seen <= pk._band_len(bq, bk, window, 10 ** 6) <= seen + 1


def test_a_token_behind_the_window_changes_nothing():
    q, k, v, _ = qkv(300, 4, 4, 64, seed=1)
    window, row = 37, 200
    far = row - window                  # the newest key the row cannot see
    k2 = k.at[:, :, :far + 1].add(3.0)
    v2 = v.at[:, :, :far + 1].add(-2.0)
    a, b = kernel(window)(q, k, v), kernel(window)(q, k2, v2)
    assert np.array_equal(np.asarray(a[:, :, row]), np.asarray(b[:, :, row]))
    assert not np.array_equal(np.asarray(a[:, :, row - 1]),
                              np.asarray(b[:, :, row - 1]))
    # and the gradient of that row's output by those keys is zero
    dk = jax.grad(lambda k: kernel(window)(q, k, v)[:, :, row].sum())(k)
    assert float(jnp.abs(dk[:, :, :far + 1]).max()) == 0.0
    assert float(jnp.abs(dk[:, :, far + 1]).max()) > 0.0


@pytest.mark.parametrize("s", [256, 300])
def test_no_window_is_the_unbanded_program_bit_for_bit(s):
    """``window=None`` traces the very program a call without the
    argument traces (full grids, no band arithmetic), and a window that
    hides nothing visits the same tiles in the same order: the same
    numbers to the last bit, forward and all three gradients."""
    q, k, v, do = qkv(s, 8, 2, 64, seed=2)

    def plain(q, k, v):
        return pk.flash_attention(q, k, v, True, None, TILE, TILE, True)

    def program(fn):
        return str(jax.make_jaxpr(lambda *x: jax.vjp(fn, *x[:3])[1](x[3]))(
            q, k, v, do))

    assert program(plain) == program(kernel(None))
    assert program(plain) != program(kernel(s))
    base, base_vjp = jax.vjp(plain, q, k, v)
    for window in (None, s, 4 * s):
        got, vjp = jax.vjp(kernel(window), q, k, v)
        assert np.array_equal(np.asarray(got), np.asarray(base)), window
        for a, b in zip(vjp(do), base_vjp(do)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), window


def test_the_graph_op_takes_a_window_and_counts_it():
    q, k, v, do = [x.transpose(0, 2, 1, 3) for x in qkv(40, 4, 2, 8)]
    sym = mx.sym.contrib.CausalAttention(
        mx.sym.Variable("q"), mx.sym.Variable("k"), mx.sym.Variable("v"),
        window=6)
    before = {n: telemetry.counter(n) for n in
              ("window_attention_traced", "causal_attention_traced")}
    ex = sym.bind(mx.cpu(), {"q": mx.nd.array(q), "k": mx.nd.array(k),
                             "v": mx.nd.array(v)},
                  args_grad={n: mx.nd.zeros(x.shape)
                             for n, x in (("q", q), ("k", k), ("v", v))})
    out = ex.forward(is_train=True)[0].asnumpy()
    ex.backward(mx.nd.array(do))
    assert telemetry.counter("window_attention_traced") > \
        before["window_attention_traced"]
    assert telemetry.counter("causal_attention_traced") == \
        before["causal_attention_traced"]
    heads = lambda *xs: [x.transpose(0, 2, 1, 3) for x in xs]   # noqa: E731
    want, vjp = jax.vjp(lambda *x: lm._masked_softmax_attention(
        *x, 8 ** -0.5, True, 6), *heads(q, k, v))
    np.testing.assert_allclose(out, heads(want)[0], rtol=1e-5, atol=1e-6)
    for name, g in zip("qkv", heads(*vjp(heads(do)[0]))):
        np.testing.assert_allclose(ex.grad_dict[name].asnumpy(), g,
                                   rtol=1e-4, atol=1e-6)
    with pytest.raises(ValueError, match="window"):
        lm._causal_attention(q, k, v, causal=False, window=4)
    with pytest.raises(ValueError, match="window"):
        pk.flash_attention(*heads(q, k, v), False, None, 128, 128, True, 4)


def test_window_scopes_name_the_device_ops():
    q, k, v, _ = [x.transpose(0, 2, 1, 3) for x in qkv(32, 2, 1, 8)]

    def scopes(window):
        jaxpr = jax.make_jaxpr(jax.grad(lambda *x: lm.causal_attention(
            *x, 0.3, True, False, window).sum(), (0, 1, 2)))(q, k, v)
        return str(jaxpr.pretty_print(name_stack=True))

    assert "window_attention_fwd" in scopes(5)
    assert "window_attention_bwd" in scopes(5)
    assert "causal_attention" not in scopes(5)
    assert "causal_attention_fwd" in scopes(None)
    assert "window_attention" not in scopes(None)


# --- the sparse-expert op at a partial share ---------------------------------

def dense_masked(x, router, w1, w3, w2, bias, first, k, scale, eps):
    """Every held expert on every token, times the token's weight for it
    (zero unless chosen): the form a partial share is judged by."""
    scores = jax.nn.sigmoid(x @ router.T)
    idx, weight = moe.topk_route(scores, k, bias, True, scale, eps)
    out = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        share = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        y = (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        out = out + y * share[:, None]
    return out


def share_case(kind, tokens=48, hidden=16, width=12, experts=16, held=4,
               first=4, seed=0):
    """Inputs whose routed rows go to the held experts all, never, or for
    nine tokens in ten."""
    rng = np.random.RandomState(seed)
    x = rng.randn(tokens, hidden).astype(np.float32)
    router = (rng.randn(experts, hidden) * 0.3).astype(np.float32)
    bias = rng.uniform(-0.1, 0.1, experts).astype(np.float32)
    mine = np.zeros(experts, bool)
    mine[first:first + held] = True
    if kind == "all":
        bias[mine] += 5.0
    elif kind == "none":
        bias[mine] -= 5.0
    else:                       # 90% of the tokens choose held experts only
        x[:, 0] = np.where(np.arange(tokens) % 10 == 9, -8.0, 8.0)
        router[:, 0] = np.where(mine, 1.0, -1.0)
    stacks = [(rng.randn(held, a, b) * 0.3).astype(np.float32)
              for a, b in ((hidden, width), (hidden, width),
                           (width, hidden))]
    return [jnp.asarray(a) for a in [x, router] + stacks + [bias]]


@pytest.mark.parametrize("kind,share", [("all", 1.0), ("none", 0.0),
                                        ("skew", 0.9)])
def test_partial_share_equals_the_dense_masked_form(kind, share):
    x, router, w1, w3, w2, bias = share_case(kind)
    first, k, experts = 4, 4, 16
    rows = x.shape[0] * k
    chunk = moe._share_rows(rows, 4, experts)
    assert chunk == 72 and -(-rows // chunk) == 3       # 1.5 x a quarter

    def op(x, router, w1, w3, w2):
        return moe.sparse_moe(x, router, w1, w3, w2, bias, experts, k, first,
                              "sigmoid", True, 2.826, 1e-20)

    def ref(x, router, w1, w3, w2):
        return dense_masked(x, router, w1, w3, w2, bias, first, k, 2.826,
                            1e-20)

    before = telemetry.counter("sparse_moe_held_rows_budget")
    (got, idx), vjp = jax.vjp(op, x, router, w1, w3, w2)
    assert telemetry.counter("sparse_moe_held_rows_budget") - before == chunk
    held = ((np.asarray(idx) >= first) & (np.asarray(idx) < first + 4))
    assert held.mean() == pytest.approx(share, abs=0.03)
    # the bound's worst case works through every chunk, the empty share
    # through none past the first
    sizes = np.bincount(np.asarray(idx).astype(int).ravel() - first,
                        minlength=experts)[:4] if share else np.zeros(4, int)
    assert (len(chunk_spans(sizes, chunk)) >= 3) == (kind != "none")
    want, ref_vjp = jax.vjp(ref, x, router, w1, w3, w2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=2e-5)
    dout = jnp.asarray(np.random.RandomState(1).randn(*x.shape), jnp.float32)
    for name, a, b in zip(("x", "router", "w1", "w3", "w2"),
                          vjp((dout, jnp.zeros_like(idx))), ref_vjp(dout)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


def test_the_bound_changes_no_number(monkeypatch):
    """One chunk of every row (no bound), the shipped chunk and a chunk
    of a twelfth of the rows give the same result and gradients."""
    x, router, w1, w3, w2, bias = share_case("skew", seed=3)

    def run(room):
        monkeypatch.setattr(moe, "_SHARE_ROOM", room)
        return jax.vjp(lambda x, w2: moe.sparse_moe(
            x, router, w1, w3, w2, bias, 16, 4, 4)[0], x, w2)

    base, base_vjp = run(8.0)
    for room in (1.5, 1 / 3):
        got, vjp = run(room)
        np.testing.assert_allclose(got, base, rtol=1e-6, atol=1e-6)
        for a, b in zip(vjp(base), base_vjp(base)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)


def chunk_spans(sizes, n):
    """[(start, stop, rows of each expert inside)] of the chunks the op
    works through: the first always, later ones while rows are left."""
    sizes = jnp.asarray(sizes, jnp.int32)
    spans, start = [], 0
    while not spans or start < int(sizes.sum()):
        stop, inside = moe._span(sizes, start, n)
        spans.append((start, int(stop), np.asarray(inside)))
        start = int(stop)
    return spans


@pytest.mark.parametrize("sizes,n,count", [
    ([0, 0, 0, 0], 8, 1), ([3, 0, 4, 1], 8, 1), ([3, 0, 4, 2], 8, 2),
    ([5, 5, 5, 5], 8, 4), ([8, 8], 8, 2), ([2, 19, 2], 8, 4),
    ([1, 1, 30], 8, 5)])
def test_a_chunk_ends_where_an_expert_ends(sizes, n, count):
    """No expert's rows are split over two chunks unless it has more than
    a chunk of its own; every row is in exactly one chunk."""
    spans = chunk_spans(sizes, n)
    assert len(spans) == count
    assert sum(inside for _, _, inside in spans).tolist() == sizes
    assert all(0 <= stop - start <= n and inside.sum() == stop - start
               for start, stop, inside in spans)
    assert [a[1] for a in spans[:-1]] == [b[0] for b in spans[1:]]
    for e, size in enumerate(sizes):
        pieces = [inside[e] for _, _, inside in spans if inside[e]]
        assert len(pieces) == max(-(-size // n), 1 if size else 0) or \
            size <= n and len(pieces) == 1


def test_whole_experts_a_chunk_sum_the_stacks_gradient_in_one_product():
    """With every expert inside one chunk an expert's gradient is one
    grouped product's, as in the single chunk of all rows: equal to the
    last float32 bits a product's own blocking leaves."""
    x, router, w1, w3, w2, bias = share_case("all")

    def grads(room, monkeypatch):
        monkeypatch.setattr(moe, "_SHARE_ROOM", room)
        return jax.grad(lambda w1, w3, w2: moe.sparse_moe(
            x, router, w1, w3, w2, bias, 16, 4, 4)[0].sum(), (0, 1, 2))(
            w1, w3, w2)

    with pytest.MonkeyPatch.context() as mp:
        whole = grads(8.0, mp)
        chunked = grads(1.5, mp)
        idx = moe.sparse_moe(x, router, w1, w3, w2, bias, 16, 4, 4)[1]
    sizes = np.bincount(np.asarray(idx).astype(int).ravel() - 4,
                        minlength=16)[:4]
    assert sizes.max() <= 72 < sizes.sum()          # several chunks, none split
    for a, b in zip(whole, chunked):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=1e-6)


def test_all_held_keeps_its_one_chunk_program():
    x, router, w1, w3, w2, bias = share_case("skew", experts=4, held=4,
                                             first=0)
    before = telemetry.counter("sparse_moe_held_rows_budget")
    text = str(jax.make_jaxpr(jax.grad(lambda x: moe.sparse_moe(
        x, router, w1, w3, w2, bias, 4, 2)[0].sum()))(x))
    assert telemetry.counter("sparse_moe_held_rows_budget") == before
    assert "while" not in text and "dynamic_slice" not in text


def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    """The share ties to the model: the op told it holds experts 0-1, 2-3,
    4-5, 6-7 in turn gives four parts that, with what every chip computes
    alike (the shared expert) counted once, are the uncut layer."""
    x, router, w1, w3, w2, bias = share_case("skew", experts=8, held=8,
                                             first=0, seed=4)
    rng = np.random.RandomState(9)
    s1, s3, s2 = [jnp.asarray(rng.randn(*shape) * 0.3, jnp.float32)
                  for shape in ((16, 12), (16, 12), (12, 16))]
    shared = (jax.nn.silu(x @ s1) * (x @ s3)) @ s2
    args = (bias, 8, 3)
    whole, idx = moe.sparse_moe(x, router, w1, w3, w2, *args)
    total = shared
    for first in range(0, 8, 2):
        part, same = moe.sparse_moe(x, router, w1[first:first + 2],
                                    w3[first:first + 2], w2[first:first + 2],
                                    *args, first_expert=first)
        assert np.array_equal(np.asarray(same), np.asarray(idx))
        total = total + part
    np.testing.assert_allclose(total, shared + whole, rtol=1e-5, atol=1e-5)


def test_route_scale_and_epsilon_reach_the_weights():
    scores = jnp.asarray(np.random.RandomState(0).uniform(0.1, 0.9, (6, 8)),
                         jnp.float32)
    top = np.sort(np.asarray(scores), axis=-1)[:, -3:].sum(-1)
    _, default = moe.topk_route(scores, 3)
    np.testing.assert_allclose(default.sum(-1), top / (top + 1e-6),
                               rtol=1e-6)
    _, weight = moe.topk_route(scores, 3, scale=2.826, eps=0.5)
    np.testing.assert_allclose(weight.sum(-1), 2.826 * top / (top + 0.5),
                               rtol=1e-6)
    # ... and the graph op's attributes reach the routine
    x, router, w1, w3, w2, bias = share_case("skew", experts=4, held=4,
                                             first=0)
    sym = mx.sym.contrib.SparseMoE(
        *[mx.sym.Variable(n) for n in ("x", "r", "w1", "w3", "w2", "b")],
        num_experts=4, num_experts_per_tok=2, routed_scaling_factor=2.826,
        norm_topk_eps=0.5)
    got = sym[0].bind(mx.cpu(), dict(zip(
        ("x", "r", "w1", "w3", "w2"),
        (mx.nd.array(a) for a in (x, router, w1, w3, w2)))),
        aux_states={"b": mx.nd.array(bias)}).forward()[0].asnumpy()
    want = dense_masked(x, router, w1, w3, w2, bias, 0, 2, 2.826, 0.5)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    plain = dense_masked(x, router, w1, w3, w2, bias, 0, 2, 1.0, 1e-6)
    assert np.abs(got - np.asarray(plain)).max() > 0.01


# --- the model's layer kinds ------------------------------------------------

def layer_outputs(cfg, probes, seed=5):
    mod = mx.mod.Module(afmoe_symbol(cfg, probes=probes), context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (2, 12), dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (2, 12),
                                    dtype=np.float32)])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    ids = np.random.RandomState(0).randint(0, 50, (2, 12)).astype(np.float32)
    mod.forward(DataBatch([mx.nd.array(ids)], [mx.nd.array(ids)]),
                is_train=True)
    return [o.asnumpy() for o in mod.get_outputs()[1:]]


def test_a_full_layer_does_not_depend_on_rope_theta():
    cfg = dict(AFMOE_TINY, num_hidden_layers=2, num_dense_layers=2,
               layer_types=["full_attention", "sliding_attention"])
    probes = ("layer0_op", "layer1_op")
    full_a, sliding_a = layer_outputs(cfg, probes)
    full_b, sliding_b = layer_outputs(dict(cfg, rope_theta=77.0), probes)
    assert np.array_equal(full_a, full_b)
    assert np.abs(sliding_a - sliding_b).max() > 1e-3


def test_a_sliding_layer_sees_its_window_and_a_full_layer_everything():
    cfg = dict(AFMOE_TINY, num_hidden_layers=1, num_dense_layers=1)
    near = layer_outputs(dict(cfg, layer_types=["sliding_attention"]),
                         ("layer0_op",))[0]
    wide = layer_outputs(dict(cfg, layer_types=["sliding_attention"],
                              sliding_window=12), ("layer0_op",))[0]
    # the first five tokens see the same keys under both windows
    np.testing.assert_allclose(near[:, :5], wide[:, :5], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(near[:, 5:] - wide[:, 5:]).max() > 1e-3
    with pytest.raises(ValueError, match="layer type"):
        afmoe_symbol(dict(cfg, layer_types=["conv"]))


def test_afmoe_symbol_names_its_probes_and_its_shares():
    with pytest.raises(ValueError, match="no probe"):
        afmoe_symbol(dict(AFMOE_TINY), probes=("layer0_choice",))
    args = afmoe_symbol(dict(AFMOE_TINY)).list_arguments()
    for name in ("layer2_gate_weight", "layer2_shared_w1_weight",
                 "layer2_post_attention_norm_gamma", "lm_head_weight",
                 "layer7_experts_w2_weight"):
        assert name in args, name
    shapes = dict(zip(args, afmoe_symbol(dict(AFMOE_TINY)).infer_shape(
        data=(1, 8), softmax_label=(1, 8))[0]))
    assert shapes["layer2_router_weight"] == (8, 32)        # all published
    assert shapes["layer2_experts_w1_weight"] == (4, 32, 16)  # those held
