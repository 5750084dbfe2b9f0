"""The sparse-expert, grouped-query attention and short-convolution ops and
the LFM2-MoE ``Symbol`` on the CPU, small and seeded, float32."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.lfm2 import LFM2_MOE_TINY, lfm2_moe_symbol
from mxnet_tpu.ops import lm, moe
from mxnet_tpu.ops import pallas_kernels as pk

E, K, H, I, T = 8, 2, 16, 12, 96


def skewed_layer(seed=0):
    """A layer whose router sends over 90% of the rows to experts 0 and 1 and
    none to 6 and 7: feature 0 of every token is 1 and carries an offset
    per expert."""
    rng = np.random.RandomState(seed)
    x = rng.randn(T, H).astype(np.float32)
    x[:, 0] = 1.0
    router = (0.3 * rng.randn(E, H)).astype(np.float32)
    router[:, 0] = [2.5, 2.5, 0, 0, 0, 0, -20, -20]
    w1, w3 = (0.3 * rng.randn(2, E, H, I)).astype(np.float32)
    w2 = (0.3 * rng.randn(E, I, H)).astype(np.float32)
    bias = rng.uniform(-0.4, 0.4, E).astype(np.float32)
    return tuple(jnp.asarray(a) for a in (x, router, w1, w3, w2, bias))


def dense_form(x, router, w1, w3, w2, bias, first=0):
    """Every held expert on every token, times the weight the token gives
    it: nothing sorted, grouped or gathered."""
    scores = jax.nn.sigmoid(x @ router.T)
    chosen = jnp.argsort(-(scores + bias), axis=-1)[:, :K]
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    table = jnp.zeros_like(scores).at[
        jnp.arange(x.shape[0])[:, None], chosen].set(picked)
    out = 0.0
    for e in range(w1.shape[0]):
        y = (jax.nn.silu(x @ w1[e]) * (x @ w3[e])) @ w2[e]
        out = out + table[:, first + e, None] * y
    return out, chosen


def layer(x, router, w1, w3, w2, bias, first=0):
    return moe.sparse_moe(x, router, w1, w3, w2, bias, num_experts=E,
                          num_experts_per_tok=K, first_expert=first)


def test_sparse_experts_are_dropless_under_skew():
    args = skewed_layer()
    out, choice = layer(*args)
    want, chosen = dense_form(*args)
    loads = np.bincount(np.asarray(choice, np.int64).ravel(), minlength=E)
    assert loads.sum() == T * K                     # every row is in a group
    assert loads[0] + loads[1] >= 0.9 * T * K       # two experts take 90%
    assert loads[6] == loads[7] == 0                # and some take none
    assert loads.max() > 3 * T * K / E              # far over any capacity
    assert np.array_equal(np.sort(np.asarray(choice), -1),
                          np.sort(np.asarray(chosen), -1))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_selection_bias_moves_the_choice_and_not_the_weights():
    x, router, w1, w3, w2, bias = skewed_layer(1)
    bias = bias.at[2].set(3.0)                      # lifts expert 2 only
    _, plain = layer(x, router, w1, w3, w2, jnp.zeros_like(bias))
    out, lifted = layer(x, router, w1, w3, w2, bias)
    assert (np.asarray(lifted) == 2).any(-1).all()  # every token takes it
    assert not (np.asarray(plain) == 2).any(-1).all()
    # the weights are the scores without the bias (the dense form's)
    np.testing.assert_allclose(
        np.asarray(out),
        np.asarray(dense_form(x, router, w1, w3, w2, bias)[0]),
        rtol=1e-5, atol=1e-5)
    scores = jax.nn.sigmoid(x @ router.T)
    idx, weight = moe.topk_route(scores, K, bias)
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx), -1)
    np.testing.assert_allclose(
        np.asarray(weight), picked / (picked.sum(-1, keepdims=True) + 1e-6),
        rtol=1e-6)


@pytest.mark.parametrize("first,held", [(0, E), (2, 2)])
def test_sparse_experts_backward_is_the_dense_forms(first, held):
    x, router, w1, w3, w2, bias = skewed_layer(2)
    rows = slice(first, first + held)
    stacks = (w1[rows], w3[rows], w2[rows])

    def loss(fn):
        return lambda x, router, w1, w3, w2: jnp.sum(
            jnp.sin(fn(x, router, w1, w3, w2, bias, first)[0]))

    got = jax.grad(loss(layer), argnums=range(5))(x, router, *stacks)
    want = jax.grad(loss(dense_form), argnums=range(5))(x, router, *stacks)
    for name, a, b in zip(("x", "router", "w1", "w3", "w2"), got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-5, err_msg=name)


def test_four_shares_of_eight_held_experts_add_up_to_the_layer():
    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(40, H), jnp.float32)
    router = jnp.asarray(rng.randn(32, H), jnp.float32)
    w1, w3 = jnp.asarray(0.3 * rng.randn(2, 32, H, I), jnp.float32)
    w2 = jnp.asarray(0.3 * rng.randn(32, I, H), jnp.float32)
    bias = jnp.asarray(rng.uniform(-0.1, 0.1, 32), jnp.float32)

    def share(first, count):
        rows = slice(first, first + count)
        return moe.sparse_moe(x, router, w1[rows], w3[rows], w2[rows], bias,
                              num_experts=32, num_experts_per_tok=4,
                              first_expert=first)

    whole, choice = share(0, 32)
    parts = [share(first, 8) for first in range(0, 32, 8)]
    for _, same in parts:                   # every share routes over all 32
        assert np.array_equal(np.asarray(same), np.asarray(choice))
    np.testing.assert_allclose(np.asarray(sum(p for p, _ in parts)),
                               np.asarray(whole), rtol=1e-5, atol=1e-5)
    with pytest.raises(ValueError, match="held from"):
        moe.sparse_moe(x, router, w1[:8], w3[:8], w2[:8], bias,
                       num_experts=32, num_experts_per_tok=4,
                       first_expert=28)


def masked_softmax(q, k, v, scale):
    """[B, H, S, d] heads, the repeat made in full."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = q.shape[2]
    score = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    score = jnp.where(jnp.tril(jnp.ones((s, s), bool)), score, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(score, -1), v)


@pytest.mark.parametrize("hkv", [1, 4])
def test_grouped_query_attention_is_the_masked_softmax(hkv):
    rng = np.random.RandomState(hkv)
    s, d = 200, 64                          # no multiple of the block
    q = jnp.asarray(rng.randn(2, 4, s, d), jnp.float32)
    k, v = jnp.asarray(rng.randn(2, 2, hkv, s, d), jnp.float32)
    do = jnp.asarray(rng.randn(2, 4, s, d), jnp.float32)
    want, vjp = jax.vjp(lambda *a: masked_softmax(*a, 0.125), q, k, v)
    got = pk.flash_attention(q, k, v, True, 0.125, 128, 128, True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    out, lse = pk._flash_fwd_impl(q, k, v, True, 0.125, 128, 128, True)
    grads = pk._flash_bwd_impl(q, k, v, out, lse, do, True, 0.125, 128,
                               True)
    for name, a, b in zip("qkv", grads, vjp(do)):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)
    # the graph op off the TPU: [B, S, H, d] in and out, the same numbers
    to_op = lambda a: a.transpose(0, 2, 1, 3)               # noqa: E731
    out, op_vjp = jax.vjp(
        lambda *a: lm.causal_attention(*a, 0.125, True, False),
        to_op(q), to_op(k), to_op(v))
    np.testing.assert_allclose(np.asarray(to_op(out)), np.asarray(want),
                               rtol=2e-4, atol=2e-5)
    for a, b in zip(op_vjp(to_op(do)), grads):
        np.testing.assert_allclose(np.asarray(to_op(a)), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_short_convolution_is_causal_and_the_three_term_sum():
    rng = np.random.RandomState(0)
    data = jnp.asarray(rng.randn(2, 10, 3 * 5), jnp.float32)
    weight = jnp.asarray(rng.randn(5, 3), jnp.float32)
    got = np.asarray(lm.short_conv(data, weight))
    bg, cg, u = np.split(np.asarray(data), 3, axis=-1)
    bu = bg * u
    want = np.zeros_like(bu)
    for t in range(10):
        for j in range(3):
            if t - 2 + j >= 0:
                want[:, t] += np.asarray(weight)[:, j] * bu[:, t - 2 + j]
    np.testing.assert_allclose(got, cg * want, rtol=1e-5, atol=1e-6)
    # changing token t changes no output before t
    moved = np.asarray(lm.short_conv(data.at[:, 6].add(1.0), weight))
    assert np.array_equal(moved[:, :6], got[:, :6])
    assert not np.array_equal(moved[:, 6], got[:, 6])


def toy_module(recompute=True, probes=("layer1_op", "layer3_ffn")):
    mod = mx.mod.Module(lfm2_moe_symbol(dict(LFM2_MOE_TINY),
                                        recompute=recompute, probes=probes),
                        context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (2, 12), dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (2, 12),
                                    dtype=np.float32)])
    mx.random.seed(11)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    return mod


def test_recomputation_segments_change_nothing():
    rng = np.random.RandomState(1)
    x = mx.nd.array(rng.randint(0, 50, (2, 12)).astype(np.float32))
    y = mx.nd.array(rng.randint(0, 50, (2, 12)).astype(np.float32))
    seen = []
    for recompute in (True, False):
        segments = telemetry.counter("executor_remat_segments")
        mod = toy_module(recompute)
        mod.forward_backward(DataBatch([x], [y]))
        assert (telemetry.counter("executor_remat_segments") > segments) \
            == recompute
        seen.append([o.asnumpy() for o in mod.get_outputs()] +
                    [g[0].asnumpy() for g in mod._exec_group.grad_arrays])
    assert len(seen[0]) == len(seen[1]) > 30
    for a, b in zip(*seen):
        assert np.array_equal(a, b)


def test_symbol_names_its_tensors_and_holds_the_bias_as_a_state():
    sym = lfm2_moe_symbol(dict(LFM2_MOE_TINY))
    assert sym.list_auxiliary_states() == [
        "layer1_expert_bias", "layer2_expert_bias", "layer3_expert_bias"]
    args = sym.list_arguments()
    assert args.count("embed_weight") == 1          # tied: one tensor
    assert "lm_head_weight" not in args
    assert {"layer0_conv_in_weight", "layer0_conv_weight",
            "layer0_mlp_w1_weight", "layer1_q_norm_gamma",
            "layer1_router_weight", "layer1_experts_w2_weight"} <= set(args)
    with pytest.raises(ValueError, match="no probe"):
        lfm2_moe_symbol(dict(LFM2_MOE_TINY), probes=("layer0_choice",))
    with pytest.raises(ValueError, match="layer_types"):
        lfm2_moe_symbol(dict(LFM2_MOE_TINY, num_hidden_layers=5))


def test_stacked_experts_are_initialised_as_separate_matrices():
    """[32, 2048, 1792] read as a convolution has fans of 3.67 M and 57 K
    and comes out ~30 x too small; as 32 matrices its scale is the
    [2048, 1792] matrix's."""
    from mxnet_tpu.initializer import InitDesc, Xavier, _conv_fans
    assert _conv_fans((32, 2048, 1792)) == (2048 * 1792, 32 * 1792)
    assert _conv_fans((32, 2048, 1792), stacked=True) == (2048, 1792)
    mx.random.seed(0)
    stack, flat, conv = (mx.nd.zeros((6, 40, 30)), mx.nd.zeros((30, 40)),
                         mx.nd.zeros((6, 40, 30)))
    Xavier()(InitDesc("experts_w1_weight", {"__stacked__": "True"}), stack)
    Xavier()(InitDesc("fc_weight"), flat)
    Xavier()(InitDesc("conv_weight"), conv)
    bound = np.sqrt(3.0 / 35.0)
    assert np.abs(stack.asnumpy()).max() == pytest.approx(bound, rel=0.02)
    assert np.abs(flat.asnumpy()).max() == pytest.approx(bound, rel=0.02)
    assert np.abs(conv.asnumpy()).max() < 0.3 * bound
    # the model's stacks carry the attribute
    attrs = lfm2_moe_symbol(dict(LFM2_MOE_TINY)).attr_dict()
    assert attrs["layer1_experts_w1_weight"]["__stacked__"] == "True"
    assert "__stacked__" not in attrs["layer1_router_weight"]
    with pytest.raises(ValueError, match="stacked"):
        Xavier()(InitDesc("w_weight", {"__stacked__": "True"}), flat)


@pytest.mark.parametrize("epochs", [1, 2])
def test_fit_takes_the_fused_step_with_the_overlap(epochs):
    rng = np.random.RandomState(2)
    x = rng.randint(0, 50, (8, 12)).astype(np.float32)
    it = mx.io.NDArrayIter(x, np.roll(x, -1, 1), batch_size=2,
                           label_name="softmax_label")
    mod = mx.mod.Module(lfm2_moe_symbol(dict(LFM2_MOE_TINY)),
                        context=mx.cpu())
    before = {k: telemetry.counter(k) for k in (
        "module_train_step", "fit_step_overlapped", "module_step_carried",
        "sparse_moe_rows")}
    bias = {}
    mod.fit(it, eval_metric="loss", num_epoch=epochs,
            initializer=mx.initializer.Xavier(),
            optimizer="sgd", optimizer_params=(("learning_rate", 0.05),),
            batch_end_callback=lambda p: bias.setdefault(
                "first", mod.get_params()[1]["layer1_expert_bias"]
                .asnumpy().copy()))
    after = {k: telemetry.counter(k) - v for k, v in before.items()}
    assert mod._cached_step is not None              # fused_step_taken
    assert after["module_train_step"] == 4 * epochs
    assert after["fit_step_overlapped"] == 3 * epochs     # (N - 1) / N
    assert after["module_step_carried"] >= 3 * epochs
    assert after["sparse_moe_rows"] % (2 * 12 * 2 * 3) == 0   # tokens x k x 3
    # the selection bias is a state that no step changes
    assert np.array_equal(
        bias["first"],
        mod.get_params()[1]["layer1_expert_bias"].asnumpy())
    assert np.abs(bias["first"]).max() <= 0.1 and bias["first"].any()
