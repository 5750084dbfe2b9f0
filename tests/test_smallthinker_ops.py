"""What the SmallThinker model asks of the ops, on the CPU at small sizes,
seeded, float32: a router that reads an operand of its own, ReGLU
experts with a hand-derived backward, softmax top-k with no selection
bias, on the all-held and the partial-share paths, and the layer kinds of
``smallthinker_symbol``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.smallthinker import (SMALLTHINKER_TINY,
                                           SMALLTHINKER_TINY_SHARE,
                                           smallthinker_symbol)
from mxnet_tpu.ops import moe

T, H, I = 60, 16, 12
EXPERTS, K = 8, 3
COUNTERS = ("sparse_moe_router_apart_traced", "sparse_moe_reglu_traced",
            "sparse_moe_traced")


def dense_reglu(x, r, router, w1, w3, w2, first=0, k=K):
    """Every held expert on every token, times the token's weight for it
    (zero unless chosen): the top k of r Wr, weighted by the softmax over
    those k logits; nothing sorted, grouped or gathered."""
    top, idx = jax.lax.top_k(r @ router.T, k)
    weight = jax.nn.softmax(top, axis=-1)
    out = jnp.zeros_like(x)
    for e in range(w1.shape[0]):
        share = jnp.sum(jnp.where(idx == first + e, weight, 0.0), axis=-1)
        y = (jax.nn.relu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        out = out + y * share[:, None]
    return out, idx


def case(seed=0, experts=EXPERTS, held=EXPERTS, first=0, skew=False):
    """(x, r, router, w1, w3, w2): the experts read x, the router r; with
    *skew* nine tokens in ten route to held experts only (feature 0 of r
    lifts their logits)."""
    rng = np.random.RandomState(seed)
    x = rng.randn(T, H).astype(np.float32)
    r = rng.randn(T, H).astype(np.float32)
    router = (rng.randn(experts, H) * 0.5).astype(np.float32)
    if skew:
        mine = np.zeros(experts, bool)
        mine[first:first + held] = True
        r[:, 0] = np.where(np.arange(T) % 10 == 9, -8.0, 8.0)
        router[:, 0] = np.where(mine, 1.0, -1.0)
    stacks = [(rng.randn(held, a, b) * 0.3).astype(np.float32)
              for a, b in ((H, I), (H, I), (I, H))]
    return [jnp.asarray(a) for a in [x, r, router] + stacks]


def op(x, r, router, w1, w3, w2, experts=EXPERTS, first=0):
    return moe.sparse_moe(x, router, w1, w3, w2, None, experts, K, first,
                          "softmax", True, 1.0, 0.0, route=r, act="relu")


def test_reglu_with_its_own_router_input_equals_the_dense_form():
    args = case()
    before = {n: telemetry.counter(n) for n in COUNTERS}
    (got, idx), vjp = jax.vjp(op, *args)
    assert {n: telemetry.counter(n) - v for n, v in before.items()} == \
        dict.fromkeys(COUNTERS, 1)
    want, ref_vjp = jax.vjp(lambda *a: dense_reglu(*a)[0], *args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    assert np.array_equal(np.sort(np.asarray(idx), -1),
                          np.sort(np.asarray(dense_reglu(*args)[1]), -1))
    # ReLU leaves a good share of the gate at zero: the mask is exercised
    x, _, _, w1 = args[:4]
    assert 0.2 < float(jnp.mean(jnp.einsum("th,ehi->eti", x, w1) > 0)) < 0.8
    dout = jnp.asarray(np.random.RandomState(1).randn(T, H), jnp.float32)
    grads = vjp((dout, jnp.zeros_like(idx)))
    for name, a, b in zip(("x", "r", "router", "w1", "w3", "w2"), grads,
                          ref_vjp(dout)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)
    # the router's gradient went to r, which the experts never read
    assert float(jnp.abs(grads[1]).max()) > 1e-3


def test_the_test_sees_where_the_router_sits():
    """The dense form whose router reads the experts' input instead of r
    (as a router after attention would) does not match the op."""
    x, r, router, w1, w3, w2 = case(seed=4)
    got = op(x, r, router, w1, w3, w2)[0]
    wrong = dense_reglu(x, x, router, w1, w3, w2)[0]
    right = dense_reglu(x, r, router, w1, w3, w2)[0]
    np.testing.assert_allclose(got, right, rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(got - wrong).max()) > 0.1


def chunk_count(sizes, n):
    sizes, start, count = jnp.asarray(sizes, jnp.int32), 0, 0
    while not count or start < int(sizes.sum()):
        start, count = int(moe._span(sizes, start, n)[0]), count + 1
    return count


def test_partial_share_under_skew_works_through_later_chunks():
    """Experts 2-5 of 8 held, nine tokens in ten routed to them only: the
    held rows outgrow the first chunk and the loop over later chunks runs,
    forward and backward, with the ReLU gate and the router apart."""
    first, held = 2, 4
    args = case(seed=2, held=held, first=first, skew=True)
    rows = T * K
    chunk = moe._share_rows(rows, held, EXPERTS)
    before = telemetry.counter("sparse_moe_held_rows_budget")
    (got, idx), vjp = jax.vjp(lambda *a: op(*a, first=first), *args)
    assert telemetry.counter("sparse_moe_held_rows_budget") - before == chunk
    mine = (np.asarray(idx) >= first) & (np.asarray(idx) < first + held)
    assert mine.mean() == pytest.approx(0.9, abs=0.03)
    sizes = np.bincount(np.asarray(idx).astype(int).ravel(),
                        minlength=EXPERTS)[first:first + held]
    assert chunk_count(sizes, chunk) >= 2
    want, ref_vjp = jax.vjp(lambda *a: dense_reglu(*a, first=first)[0],
                            *args)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    dout = jnp.asarray(np.random.RandomState(3).randn(T, H), jnp.float32)
    for name, a, b in zip(("x", "r", "router", "w1", "w3", "w2"),
                          vjp((dout, jnp.zeros_like(idx))), ref_vjp(dout)):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5, err_msg=name)


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer():
    """The share ties to the model: the op told it holds experts 0-1, 2-3,
    4-5, 6-7 in turn routes every token as the uncut layer does, and the
    four parts add up to the uncut layer's result."""
    x, r, router, w1, w3, w2 = case(seed=5)
    whole, idx = op(x, r, router, w1, w3, w2)
    total = jnp.zeros_like(whole)
    for first in range(0, EXPERTS, 2):
        cut = slice(first, first + 2)
        part, same = op(x, r, router, w1[cut], w3[cut], w2[cut],
                        first=first)
        assert np.array_equal(np.asarray(same), np.asarray(idx))
        total = total + part
    np.testing.assert_allclose(total, whole, rtol=1e-5, atol=1e-5)


def test_softmax_weights_without_epsilon_are_the_softmax_of_the_chosen():
    logits = jnp.asarray(np.random.RandomState(6).randn(9, EXPERTS) * 3,
                         jnp.float32)
    idx, weight = moe.topk_route(jax.nn.softmax(logits, -1), K, None, True,
                                 1.0, 0.0)
    top, want_idx = jax.lax.top_k(logits, K)
    assert np.array_equal(np.asarray(idx), np.asarray(want_idx))
    np.testing.assert_allclose(weight, jax.nn.softmax(top, -1), rtol=1e-6)


def test_the_graph_op_takes_the_routing_operand_and_no_bias():
    x, r, router, w1, w3, w2 = case(seed=7)
    names = ("x", "r", "router", "w1", "w3", "w2")
    sym = mx.sym.contrib.SparseMoE(
        mx.sym.Variable("x"), router_weight=mx.sym.Variable("router"),
        w1_weight=mx.sym.Variable("w1"), w3_weight=mx.sym.Variable("w3"),
        w2_weight=mx.sym.Variable("w2"), route_data=mx.sym.Variable("r"),
        num_experts=EXPERTS, num_experts_per_tok=K, scoring="softmax",
        norm_topk_eps=0.0, act_type="relu", route_apart=True, no_bias=True)
    assert sym.list_arguments() == ["x", "router", "w1", "w3", "w2", "r"]
    assert sym.list_auxiliary_states() == []
    values = dict(zip(names, (x, r, router, w1, w3, w2)))
    ex = sym[0].bind(mx.cpu(), {n: mx.nd.array(v) for n, v in values.items()},
                     args_grad={n: mx.nd.zeros(v.shape)
                                for n, v in values.items()})
    got = ex.forward(is_train=True)[0].asnumpy()
    dout = np.random.RandomState(8).randn(T, H).astype(np.float32)
    ex.backward(mx.nd.array(dout))
    want, vjp = jax.vjp(lambda *a: dense_reglu(*a)[0],
                        *(values[n] for n in names))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    for name, g in zip(names, vjp(jnp.asarray(dout))):
        np.testing.assert_allclose(ex.grad_dict[name].asnumpy(), g,
                                   rtol=2e-4, atol=2e-5, err_msg=name)
    # the default is the SwiGLU op that routes from its data, with a bias
    plain = mx.sym.contrib.SparseMoE(mx.sym.Variable("x"), num_experts=4,
                                     name="plain")
    assert plain.list_arguments() == [
        "x", "plain_router_weight", "plain_w1_weight", "plain_w3_weight",
        "plain_w2_weight"]
    assert plain.list_auxiliary_states() == ["plain_expert_bias"]
    with pytest.raises(ValueError, match="act"):
        moe.sparse_moe(x, router, w1, w3, w2, None, EXPERTS, K, act="gelu")
    with pytest.raises(ValueError, match="routing input"):
        moe.sparse_moe(x, router, w1, w3, w2, None, EXPERTS, K,
                       route=r[:-1])


def test_the_nd_op_differentiates_the_routing_operand():
    """On the imperative path the routing operand (input 5) has a
    gradient: the selection bias, when there is one, is kept out by the
    op, not by position."""
    x, r, router, w1, w3, w2 = [mx.nd.array(a) for a in case(seed=9)]
    for a in (x, r):
        a.attach_grad()
    with mx.autograd.record():
        out = mx.nd.contrib.SparseMoE(
            x, router, w1, w3, w2, r, num_experts=EXPERTS,
            num_experts_per_tok=K, scoring="softmax", norm_topk_eps=0.0,
            act_type="relu", route_apart=True, no_bias=True)[0]
        loss = (out * out).sum()
    loss.backward()
    want = jax.grad(lambda xx, rr: jnp.sum(dense_reglu(
        xx, rr, router._data, w1._data, w3._data, w2._data)[0] ** 2),
        (0, 1))(x._data, r._data)
    for got, g in zip((x.grad, r.grad), want):
        np.testing.assert_allclose(got.asnumpy(), g, rtol=2e-4, atol=2e-5)
    assert float(np.abs(r.grad.asnumpy()).max()) > 1e-3


# --- the model's layer kinds ------------------------------------------------

def layer_outputs(cfg, probes, seed=5, seq=12):
    mod = mx.mod.Module(smallthinker_symbol(cfg, probes=probes),
                        context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (2, seq), dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (2, seq),
                                    dtype=np.float32)])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    ids = np.random.RandomState(0).randint(0, 50, (2, seq)) \
        .astype(np.float32)
    mod.forward(DataBatch([mx.nd.array(ids)], [mx.nd.array(ids)]),
                is_train=True)
    return [o.asnumpy() for o in mod.get_outputs()[1:]]


def test_rope_follows_its_layout_and_the_window_its_own():
    cfg = dict(SMALLTHINKER_TINY, num_hidden_layers=2,
               sliding_window_layout=[0, 1], rope_layout=[0, 1])
    probes = ("layer0_op", "layer1_op")
    full_a, sliding_a = layer_outputs(cfg, probes)
    full_b, sliding_b = layer_outputs(dict(cfg, rope_theta=77.0), probes)
    assert np.array_equal(full_a, full_b)            # NoPE
    assert np.abs(sliding_a - sliding_b).max() > 1e-3
    # the window: the first five tokens see the same keys under both
    near = layer_outputs(cfg, ("layer1_op",))[0]
    wide = layer_outputs(dict(cfg, sliding_window_size=12),
                         ("layer1_op",))[0]
    np.testing.assert_allclose(near[:, :5], wide[:, :5], rtol=1e-5,
                               atol=1e-6)
    assert np.abs(near[:, 5:] - wide[:, 5:]).max() > 1e-3


def test_smallthinker_symbol_names_its_tensors_and_counts_its_ops():
    with pytest.raises(ValueError, match="no probe"):
        smallthinker_symbol(dict(SMALLTHINKER_TINY), probes=("layer9_op",))
    with pytest.raises(ValueError, match="layouts"):
        smallthinker_symbol(dict(SMALLTHINKER_TINY, rope_layout=[0, 1]))
    sym = smallthinker_symbol(dict(SMALLTHINKER_TINY_SHARE))
    assert sym.list_auxiliary_states() == []        # no selection bias
    args = sym.list_arguments()
    for name in ("layer0_input_norm_gamma", "layer0_post_attention_norm_gamma",
                 "layer0_router_weight", "lm_head_weight", "embed_weight"):
        assert name in args, name
    assert not any("gate" in a or "shared" in a or "q_norm" in a
                   for a in args)
    shapes = dict(zip(args, sym.infer_shape(data=(1, 8),
                                            softmax_label=(1, 8))[0]))
    assert shapes["layer2_router_weight"] == (8, 32)        # all scored
    assert shapes["layer2_experts_w1_weight"] == (4, 32, 16)  # those held
    assert shapes["layer2_q_weight"] == (56, 32)     # 7 heads of 8
    before = {n: telemetry.counter(n) for n in COUNTERS}
    layer_outputs(dict(SMALLTHINKER_TINY), ("layer0_op",))
    moved = {telemetry.counter(n) - v for n, v in before.items()}
    # every expert op of each trace routes apart and is ReGLU
    assert len(moved) == 1
    assert moved.pop() in (8, 16, 24)
