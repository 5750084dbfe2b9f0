"""The state-space scan, the biased causal convolution and the Granite
hybrid graph on the CPU at toy size, float32, seeded: the scan in three
forms (token-by-token recurrence, dual masked form, chunked in ``jnp`` and
as the Pallas kernels in interpret mode), forward and all six gradients;
the state at a chunk's end; a kernel with the carried state zeroed, which
must fail; the convolution against ``lax.conv_general_dilated``;
``_contrib_ShortConv`` bit for bit; the four multipliers; attention
without positions."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.io import DataBatch, DataDesc
from mxnet_tpu.models.granite import GRANITE_TINY, granite_hybrid_symbol
from mxnet_tpu.ops import lm
from mxnet_tpu.ops import pallas_kernels as pk

HIGHEST = jax.default_matmul_precision("highest")


def operands(seed, batch, seq, heads, p, groups, n, dt_range=(1e-3, 0.1)):
    rng = np.random.RandomState(seed)
    f32 = jnp.float32
    dt = np.exp(rng.uniform(*np.log(dt_range), (batch, seq, heads)))
    return (jnp.asarray(rng.randn(batch, seq, heads, p), f32),
            jnp.asarray(dt, f32),
            jnp.asarray(np.log(rng.uniform(1, 16, heads)), f32),
            jnp.asarray(rng.randn(batch, seq, groups, n), f32),
            jnp.asarray(rng.randn(batch, seq, groups, n), f32),
            jnp.asarray(rng.randn(heads), f32))


def recurrence(x, dt, a_log, b, c, d, states_at=None):
    """H_t = exp(a_t) H_{t-1} + dt_t X_t B_t^T, y_t = H_t C_t + D X_t, a
    token at a time; with *states_at* also the state after those
    tokens."""
    heads, groups = x.shape[2], b.shape[2]
    a = -jnp.exp(a_log)
    bh = jnp.repeat(b, heads // groups, axis=2)
    ch = jnp.repeat(c, heads // groups, axis=2)

    def step(state, v):
        x_t, dt_t, b_t, c_t = v
        state = jnp.exp(dt_t * a)[..., None, None] * state + \
            dt_t[..., None, None] * x_t[..., :, None] * b_t[..., None, :]
        return state, (jnp.einsum("bhpn,bhn->bhp", state, c_t) +
                       d[:, None] * x_t, state)

    zero = jnp.zeros(x.shape[:1] + x.shape[2:] + b.shape[3:])
    _, (y, states) = jax.lax.scan(step, zero, tuple(
        jnp.swapaxes(v, 0, 1) for v in (x, dt, bh, ch)))
    y = jnp.swapaxes(y, 0, 1)
    return y if states_at is None else (y, states[np.asarray(states_at)])


def dual(x, dt, a_log, b, c, d):
    """y_t = sum_{s<=t} exp(c_t - c_s) (C_t . B_s) dt_s X_s + D X_t with
    the [S, S] decay matrix whole."""
    heads, groups = x.shape[2], b.shape[2]
    run = jnp.cumsum(dt * -jnp.exp(a_log), axis=1)           # [B, S, H]
    s = x.shape[1]
    keep = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    decay = jnp.exp(jnp.where(keep[None, :, :, None],
                              run[:, :, None] - run[:, None], -jnp.inf))
    score = jnp.repeat(jnp.einsum("btgn,bsgn->btsg", c, b),
                       heads // groups, axis=3)
    return jnp.einsum("btsh,bshp->bthp", decay * score * dt[:, None], x) + \
        d[:, None] * x


def chunked(kernel, chunk, heads=16):
    def fn(*v):
        return pk.state_space_scan(*v, chunk, kernel, kernel, heads)
    return fn


def value_and_grads(fn, args, weight):
    with HIGHEST:
        return fn(*args), jax.grad(lambda *v: jnp.sum(fn(*v) * weight),
                                   argnums=range(6))(*args)


def close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    return np.linalg.norm(got - want) <= tol * np.linalg.norm(want) + 1e-12


NAMES = ("x", "dt", "a_log", "b", "c", "d")
CASES = [
    # seq, chunk, heads, groups, dt range
    (5, 8, 4, 1, (1e-3, 0.1)),          # shorter than a chunk
    (40, 8, 4, 1, (1e-3, 0.1)),         # five chunks
    (40, 16, 4, 2, (1e-3, 0.1)),        # a ragged last chunk, two groups
    (70, 64, 4, 2, (1e-3, 0.1)),
    (40, 8, 4, 1, (1e-5, 1e-4)),        # decays near 1
    (40, 8, 4, 2, (0.5, 3.0)),          # decays near 0
]


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("seq,chunk,heads,groups,dt_range", CASES)
def test_scan_in_three_forms(seq, chunk, heads, groups, dt_range, kernel):
    args = operands(seq, 2, seq, heads, 16, groups, 16, dt_range)
    weight = jnp.asarray(np.random.RandomState(1).randn(2, seq, heads, 16),
                         jnp.float32)
    y0, g0 = value_and_grads(recurrence, args, weight)
    y1, g1 = value_and_grads(dual, args, weight)
    y2, g2 = value_and_grads(chunked(kernel, chunk), args, weight)
    assert close(y1, y0) and close(y2, y0)
    for name, a, b_, c_ in zip(NAMES, g0, g1, g2):
        # a_log and dt gather cancelling terms over the whole sequence
        tol = 2e-4 if name in ("a_log", "dt") else 2e-5
        assert close(b_, a, tol), ("dual", name)
        assert close(c_, a, tol), ("chunked", name)


@pytest.mark.parametrize("kernel", [False, True], ids=["jnp", "pallas"])
def test_state_at_a_chunks_end_is_the_recurrences(kernel):
    args = operands(3, 2, 40, 4, 16, 2, 16)
    x, dt, a_log, b, c, d = args
    ends = [7, 15, 23, 31]
    with HIGHEST:
        _, want = recurrence(*args, states_at=ends)      # [4, B, H, P, N]
        xp, dtp, cs, bp, cp, n = pk._ssm_prepare(x, dt, a_log, b, c, 8)
        if kernel:
            got = pk._ssm_pallas(xp, dtp, cs, bp, cp, d, n, 16, True,
                                 states=True)            # [B, n, N, H * P]
            got = got.reshape(2, n, 16, 4, 16).transpose(1, 0, 3, 4, 2)
        else:
            xs = tuple(pk._ssm_chunks(n, 2, v) for v in (xp, dtp, cs)) + \
                (pk._ssm_chunks(n, 2, bp, False),)
            got = jax.lax.scan(
                lambda S, v: (pk._ssm_advance(S, *v), S),
                pk._ssm_zero_state(xp, bp), xs)[1]       # [n, B, G, R, N, P]
            got = got.reshape(n, 2, 4, 16, 16).swapaxes(-1, -2)
    # chunk j starts from the state after token 8 j - 1
    assert float(jnp.abs(got[0]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(got[1:]), np.asarray(want),
                               rtol=2e-5, atol=2e-6)


def test_a_kernel_that_forgets_the_carried_state_fails():
    """Guards the tests above: a kernel that starts every chunk from a
    zero state (here: the kernel given each chunk as a sequence of its
    own) leaves the recurrence's outputs by far more than rounding, and
    only past the first chunk."""
    args = operands(5, 2, 40, 4, 16, 1, 16)
    x, dt, a_log, b, c, d = args
    with HIGHEST:
        want = recurrence(*args)
        xp, dtp, cs, bp, cp, n = pk._ssm_prepare(x, dt, a_log, b, c, 8)
        whole = pk._ssm_pallas(xp, dtp, cs, bp, cp, d, n, 16, True)
        apart = [v.reshape((2 * n, 8) + v.shape[2:])
                 for v in (xp, dtp, cs, bp, cp)]
        forgets = pk._ssm_pallas(*apart, d, 1, 16, True).reshape(xp.shape)
    assert close(whole[:, :40], want)
    assert not close(forgets[:, :40], want, 1e-2)
    assert close(forgets[:, :8], want[:, :8])


def test_chunk_changes_nothing_beyond_rounding():
    args = operands(7, 1, 70, 4, 16, 2, 16)
    weight = jnp.ones((1, 70, 4, 16), jnp.float32)
    base = value_and_grads(chunked(False, 8), args, weight)
    for kernel, chunk in ((False, 16), (False, 64), (True, 16), (True, 128)):
        y, grads = value_and_grads(chunked(kernel, chunk), args, weight)
        assert close(y, base[0])
        for name, a, b_ in zip(NAMES, grads, base[1]):
            assert close(a, b_, 2e-4), (kernel, chunk, name)


def test_heads_a_program_change_nothing():
    """Two, four and eight heads a program over eight heads a group: the
    state of every block of heads is carried apart."""
    args = operands(9, 1, 24, 8, 16, 1, 16)
    with HIGHEST:
        want = recurrence(*args)
        for heads in (2, 4, 8):
            assert close(chunked(True, 8, heads)(*args), want), heads
    with pytest.raises(ValueError, match="do not tile"):
        pk._ssm_tiling(12, 1, 16, 8)


def test_scan_op_counts_and_keeps_float32_inside():
    x, dt, a_log, b, c, d = operands(2, 1, 20, 4, 16, 1, 16)
    before = {k: telemetry.counter(k)
              for k in ("state_space_traced", "state_space_chunks")}
    half = jnp.bfloat16
    y = mx.nd.contrib.StateSpaceScan(
        mx.nd.array(x.astype(half), dtype=half), mx.nd.array(dt),
        mx.nd.array(a_log), mx.nd.array(b.astype(half), dtype=half),
        mx.nd.array(c.astype(half), dtype=half), mx.nd.array(d), chunk=8)
    assert y.dtype == half and y.shape == (1, 20, 4, 16)
    assert telemetry.counter("state_space_traced") - \
        before["state_space_traced"] == 1
    assert telemetry.counter("state_space_chunks") - \
        before["state_space_chunks"] == 3
    with HIGHEST:
        want = recurrence(x.astype(half).astype(jnp.float32), dt, a_log,
                          b.astype(half).astype(jnp.float32),
                          c.astype(half).astype(jnp.float32), d)
    # bf16 operands, float32 decay and state: a bf16 decay would lose
    # 2^-8 a token and be out by tens of percent after a chunk
    assert close(y.asnumpy().astype(np.float32), want, 2e-2)
    jaxpr = str(jax.make_jaxpr(
        lambda *v: pk.state_space_scan(*v, 8))(
        x.astype(half), dt, a_log, b.astype(half), c.astype(half), d))
    assert "exp" in jaxpr and "bf16[1,3,8,1,4]{} = exp" not in jaxpr


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("biased", [False, True], ids=["plain", "biased"])
def test_causal_conv_is_a_left_padded_depthwise_convolution(taps, biased):
    rng = np.random.RandomState(taps)
    x = jnp.asarray(rng.randn(2, 11, 6), jnp.float32)
    w = jnp.asarray(rng.randn(6, taps), jnp.float32)
    bias = jnp.asarray(rng.randn(6), jnp.float32) if biased else None
    with HIGHEST:
        want = jax.lax.conv_general_dilated(
            x, w.T[:, None, :], (1,), [(taps - 1, 0)],
            dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=6)
        if biased:
            want = want + bias
        got = lm.causal_taps(x, w, bias)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-6)
    inputs = [mx.nd.array(x), mx.nd.array(w)] + \
        ([mx.nd.array(bias)] if biased else [])
    for act, fn in (("silu", jax.nn.silu), (None, lambda v: v)):
        out = mx.nd.contrib.CausalConv1D(*inputs, act_type=act,
                                         no_bias=not biased)
        np.testing.assert_allclose(out.asnumpy(), np.asarray(fn(want)),
                                   rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="act_type"):
        mx.nd.contrib.CausalConv1D(*inputs, act_type="relu",
                                   no_bias=not biased)


def old_short_conv(data, weight):
    """``short_conv`` as it stood before the taps were shared (PR 36)."""
    taps = weight.shape[1]
    bg, cg, u = jnp.split(data, 3, axis=-1)
    bu = jnp.pad((bg * u).astype(jnp.float32),
                 [(0, 0), (taps - 1, 0), (0, 0)])
    s = data.shape[1]
    w = weight.astype(jnp.float32)
    c = sum(w[:, j] * bu[:, j:j + s] for j in range(taps))
    return cg * c.astype(data.dtype)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_short_conv_gives_the_results_it_gave(dtype):
    rng = np.random.RandomState(2)
    data = jnp.asarray(rng.randn(2, 13, 24), dtype)
    weight = jnp.asarray(rng.randn(8, 3), dtype)
    got = mx.nd.contrib.ShortConv(mx.nd.array(data, dtype=data.dtype),
                                  mx.nd.array(weight, dtype=data.dtype))
    want = old_short_conv(data, weight)
    assert got.dtype == data.dtype
    assert np.array_equal(got.asnumpy().astype(np.float32),
                          np.asarray(want.astype(jnp.float32)))
    grads = jax.grad(lambda d, w: jnp.sum(lm.short_conv(d, w)
                                          .astype(jnp.float32)), (0, 1))
    old = jax.grad(lambda d, w: jnp.sum(old_short_conv(d, w)
                                        .astype(jnp.float32)), (0, 1))
    for a, b_ in zip(grads(data, weight), old(data, weight)):
        assert np.array_equal(np.asarray(a.astype(jnp.float32)),
                              np.asarray(b_.astype(jnp.float32)))


def bound(cfg, batch, seq, probes=(), seed=3):
    mod = mx.mod.Module(granite_hybrid_symbol(cfg, probes=probes),
                        context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (batch, seq), dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch, seq),
                                    dtype=np.float32)])
    mx.random.seed(seed)
    mod.init_params(initializer=mx.initializer.Xavier(magnitude=6))
    return mod


def outputs(mod, x):
    y = np.roll(x, -1, axis=1)
    mod.forward(DataBatch([mx.nd.array(x)], [mx.nd.array(y)]),
                is_train=False)
    return [o.asnumpy() for o in mod.get_outputs()]


def toy_ids(batch, seq, vocab, seed=0):
    return np.random.RandomState(seed).randint(0, vocab, (batch, seq)) \
        .astype(np.float32)


@pytest.mark.parametrize("key", ["embedding_multiplier",
                                 "residual_multiplier",
                                 "attention_multiplier", "logits_scaling"])
def test_each_multiplier_reaches_the_loss(key):
    x = toy_ids(2, 40, 96)
    base = outputs(bound(dict(GRANITE_TINY), 2, 40), x)[0][0]
    again = outputs(bound(dict(GRANITE_TINY), 2, 40), x)[0][0]
    moved = outputs(bound(dict(GRANITE_TINY,
                               **{key: GRANITE_TINY[key] * 1.5}), 2, 40),
                    x)[0][0]
    # the same graph gives the same bits, so any movement is the key's (the
    # norms take most of the embedding's scale away again: 3e-4 there)
    assert base == again
    assert abs(moved - base) > 5e-5, (key, base, moved)


def test_graph_has_the_published_layers_and_no_rotary_embedding():
    sym = granite_hybrid_symbol(dict(GRANITE_TINY))
    ops = [n["op"] for n in __import__("json").loads(sym.tojson())["nodes"]]
    assert ops.count("_contrib_StateSpaceScan") == 9
    assert ops.count("_contrib_CausalConv1D") == 9
    assert ops.count("_contrib_CausalAttention") == 1
    assert ops.count("_contrib_BlockedSoftmaxCE") == 1
    assert not any("Rotary" in op for op in ops)
    args = sym.list_arguments()
    assert "layer5_q_weight" in args and "layer5_in_proj_weight" not in args
    assert "lm_head_weight" not in args          # the head is the embedding
    for name in ("a_log", "dt_bias", "d", "conv_bias", "mixer_norm_gamma"):
        assert "layer4_" + name in args
    with pytest.raises(ValueError, match="no probe"):
        granite_hybrid_symbol(dict(GRANITE_TINY), probes=("layer0_choice",))
    with pytest.raises(ValueError, match="mamba_conv_bias"):
        granite_hybrid_symbol(dict(GRANITE_TINY, mamba_conv_bias=False))


def test_attention_sees_no_positions_beyond_the_mask():
    """One attention layer alone: with no rotary embedding a query's
    output is a function of the SET of tokens before it, so shuffling
    them leaves the last token's heads unchanged (and moves earlier
    ones); a graph with positions in it would move it."""
    cfg = dict(GRANITE_TINY, num_hidden_layers=1, layer_types=["attention"])
    mod = bound(cfg, 1, 12, probes=("layer0_op",))
    x = toy_ids(1, 12, 96, seed=4)
    shuffled = x.copy()
    shuffled[0, :11] = x[0, np.random.RandomState(5).permutation(11)]
    a, b_ = outputs(mod, x)[1], outputs(mod, shuffled)[1]
    np.testing.assert_allclose(a[0, 11], b_[0, 11], rtol=1e-5, atol=1e-6)
    assert np.abs(a[0, 5] - b_[0, 5]).max() > 1e-3
    # the scale is the multiplier, not 1 / sqrt(head size)
    assert cfg["attention_multiplier"] != (64 // 4) ** -0.5


def test_scan_initializer_draws_the_published_range():
    cfg = dict(GRANITE_TINY, mamba_n_heads=4)
    mod = bound(cfg, 1, 8)
    args = mod.get_params()[0]
    a = np.concatenate([args["layer%d_a_log" % i].asnumpy()
                        for i in (0, 1, 2, 3, 4, 6, 7, 8, 9)])
    bias = np.concatenate([args["layer%d_dt_bias" % i].asnumpy()
                           for i in (0, 1, 2, 3, 4, 6, 7, 8, 9)])
    assert a.dtype == np.float32 and 0 <= a.min() and a.max() <= np.log(16)
    dt = np.log1p(np.exp(bias))
    assert 0.001 * 0.999 <= dt.min() and dt.max() <= 0.1 * 1.001
    assert np.all(args["layer0_d"].asnumpy() == 1.0)
    assert np.abs(args["layer0_conv_bias"].asnumpy()).max() <= 0.5
    assert np.abs(args["layer0_conv_bias"].asnumpy()).max() > 0.1
    with pytest.raises(ValueError, match="a_log or dt_bias"):
        mx.initializer.StateSpaceInit("d")
