"""Language-model ops (RMSNorm, rotary embedding, power retention, blocked
softmax cross-entropy), recomputation segments in the executor and the
lazily allocated gradient buffers."""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.executor import _build_graph_fn
from mxnet_tpu.ops import lm
from mxnet_tpu.ops import pallas_kernels as pk
from mxnet_tpu.ops.lm import blocked_softmax_ce, rms_norm
from mxnet_tpu.ops.pallas_kernels import power_retention
from mxnet_tpu.symbol.symbol import _topo

EPS = 1e-6


def retention_quadratic(q, k, v, a, eps=EPS):
    """o_t = sum_s w_ts v_s / (sum_s w_ts + eps),
    w_ts = (q_t.k_s / sqrt d)^2 exp(c_t - c_s), s <= t."""
    b, s, hq, d = q.shape
    g = hq // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    c = jnp.repeat(jnp.cumsum(a, axis=1), g, axis=2).transpose(0, 2, 1)
    score = jnp.einsum("bthd,bshd->bhts", q, k,
                       precision="highest") / math.sqrt(d)
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    w = score ** 2 * jnp.exp(jnp.where(
        causal, c[..., :, None] - c[..., None, :], -jnp.inf))
    num = jnp.einsum("bhts,bshv->bthv", w, v, precision="highest")
    return num / (w.sum(-1).transpose(0, 2, 1) + eps)[..., None]


def phi(u):
    """The d(d+1)/2 distinct entries of u u^T, off-diagonal ones times
    sqrt 2, so that phi(q).phi(k) = (q.k)^2."""
    i, j = np.triu_indices(u.shape[-1])
    return u[..., i] * u[..., j] * np.where(i == j, 1.0, math.sqrt(2.0))


def retention_recurrent(q, k, v, a, eps=EPS):
    """Token by token: S_t = e^{a_t} S_{t-1} + phi(k_t) v_t^T,
    z_t = e^{a_t} z_{t-1} + phi(k_t); o_t = phi(q_t)^T S_t / (phi(q_t)^T
    z_t + eps).  numpy, float64."""
    q, k, v, a = (np.asarray(x, np.float64) for x in (q, k, v, a))
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    size = d * (d + 1) // 2
    out = np.zeros((b, s, hq, v.shape[-1]))
    for bi in range(b):
        for h in range(hkv):
            S, z = np.zeros((size, v.shape[-1])), np.zeros(size)
            for t in range(s):
                pk = phi(k[bi, t, h])
                S = math.exp(a[bi, t, h]) * S + pk[:, None] * v[bi, t, h]
                z = math.exp(a[bi, t, h]) * z + pk
                for gi in range(hq // hkv):
                    pq = phi(q[bi, t, h * (hq // hkv) + gi]) / d
                    out[bi, t, h * (hq // hkv) + gi] = pq @ S / (pq @ z + eps)
    return out


def retention_inputs(seed, s, gate_shift, b=2, hq=4, hkv=2, d=8):
    rng = np.random.RandomState(seed)
    q, k, v = (jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
               for h in (hq, hkv, hkv))
    a = jax.nn.log_sigmoid(jnp.asarray(rng.randn(b, s, hkv) + gate_shift,
                                       jnp.float32))
    return q, k, v, a


@pytest.mark.parametrize("s,chunk,gate_shift", [
    (24, 8, 3.0),       # gates near 1
    (24, 8, -6.0),      # gates near 0: the state is forgotten at once
    (21, 8, 1.0),       # S no multiple of the chunk
    (19, 4, 0.0), (16, 16, 2.0), (10, 32, 2.0)])
def test_quadratic_recurrent_and_chunked_forms_agree(s, chunk, gate_shift):
    q, k, v, a = retention_inputs(s, s, gate_shift)
    quad = np.asarray(retention_quadratic(q, k, v, a))
    np.testing.assert_allclose(retention_recurrent(q, k, v, a), quad,
                               rtol=2e-4, atol=2e-6)
    np.testing.assert_allclose(np.asarray(power_retention(q, k, v, a, chunk)),
                               quad, rtol=2e-4, atol=2e-6)


@pytest.mark.parametrize("s,chunk,gate_shift", [(24, 8, 3.0), (21, 8, -2.0),
                                                (13, 16, 1.0)])
def test_chunked_backward_equals_grad_of_the_quadratic_form(s, chunk,
                                                            gate_shift):
    q, k, v, a = retention_inputs(7, s, gate_shift)
    mix = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    want = jax.grad(lambda *x: (retention_quadratic(*x) * mix).sum(),
                    (0, 1, 2, 3))(q, k, v, a)
    got = jax.grad(lambda *x: (power_retention(*x, chunk) * mix).sum(),
                   (0, 1, 2, 3))(q, k, v, a)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) <= 2e-4 * float(jnp.abs(w).max())


def test_pallas_forward_agrees_in_interpret_mode():
    # the kernel's tiles are whole lane tiles: head size and chunk 128
    q, k, v, a = retention_inputs(11, 300, 3.0, b=1, hq=2, hkv=1, d=128)
    want = retention_quadratic(q, k, v, a)
    got = power_retention(q, k, v, a, 128, EPS, True, True)
    assert float(jnp.abs(got - want).max()) <= 1e-4 * float(
        jnp.abs(want).max())
    with pytest.raises(ValueError, match="multiples of 128"):
        power_retention(q, k, v, a, 64, EPS, True, True)


def emitting_scan(qh, kh, vh, ah, scale, eps):
    """The scan as it was while the forward saved the chunk states: a
    chunk's outputs and the state it started from, side by side."""
    bh, d, dv = kh.shape[0], kh.shape[-1], vh.shape[-1]
    step = jax.vmap(functools.partial(pk._retention_chunk, scale=scale,
                                      eps=eps))

    def body(carry, xs):
        S, Z = carry
        S1, Z1, o = step(S, Z, *xs)
        return (S1, Z1), (o, S.astype(qh.dtype), Z.astype(qh.dtype))

    init = (jnp.zeros((bh, d, dv, d), jnp.float32),
            jnp.zeros((bh, d, d), jnp.float32))
    xs = tuple(jnp.moveaxis(x, 1, 0) for x in (qh, kh, vh, ah))
    _, out = jax.lax.scan(body, init, xs)
    return tuple(jnp.moveaxis(x, 0, 1) for x in out)


@pytest.mark.parametrize("form,s,chunk,hq,hkv,d", [
    ("jnp", 24, 8, 2, 2, 8),            # whole chunks, a query head a key
    ("jnp", 21, 8, 4, 2, 8),            # a ragged tail, grouped heads
    ("pallas", 300, 128, 4, 2, 128)])   # the same through the kernel
def test_states_pass_remakes_the_saved_states_bit_for_bit(form, s, chunk, hq,
                                                          hkv, d):
    q, k, v, a = retention_inputs(5, s, 2.0, b=1, hq=hq, hkv=hkv, d=d)
    qh, kh, vh, ah = pk._retention_heads(q, k, v, a, chunk)
    scale = 1.0 / math.sqrt(d)
    o, S0, Z0 = emitting_scan(qh, kh, vh, ah, scale, EPS)
    if form == "pallas":
        got = pk._retention_states_pallas(kh, vh, ah, True)
        np.testing.assert_allclose(
            pk._retention_pallas(qh, kh, vh, ah, scale, EPS, True), o,
            rtol=1e-4, atol=1e-6)
    else:
        got = pk._retention_states_scan(kh, vh, ah)
        np.testing.assert_array_equal(
            pk._retention_scan(qh, kh, vh, ah, scale, EPS), o)
    assert float(jnp.abs(S0[:, -1]).max()) > 0 and S0.shape[1] == -(-s // chunk)
    np.testing.assert_array_equal(got[0], S0)
    np.testing.assert_array_equal(got[1], Z0)


def test_pallas_states_pass_carries_the_gradients_in_interpret_mode():
    q, k, v, a = retention_inputs(13, 200, 2.0, b=1, hq=2, hkv=1, d=128)
    mix = jnp.cos(jnp.arange(q.size, dtype=jnp.float32)).reshape(q.shape)
    want = jax.grad(lambda *x: (retention_quadratic(*x) * mix).sum(),
                    (0, 1, 2, 3))(q, k, v, a)
    got = jax.grad(lambda *x: (power_retention(*x, 128, EPS, True, True)
                               * mix).sum(), (0, 1, 2, 3))(q, k, v, a)
    for g, w in zip(got, want):
        assert float(jnp.abs(g - w).max()) <= 2e-4 * float(jnp.abs(w).max())


def test_states_pass_is_counted_once_a_backward_trace():
    q, k, v, a = retention_inputs(2, 20, 2.0)

    def traces(fn):
        before = telemetry.counter("power_retention_states_traced")
        jax.make_jaxpr(fn)(q, k, v, a)
        return telemetry.counter("power_retention_states_traced") - before

    assert traces(lambda *x: power_retention(*x, 8)) == 0
    assert traces(jax.grad(lambda *x: power_retention(*x, 8).sum(),
                           (0, 1, 2, 3))) == 1
    assert "power_retention_states_traced" in telemetry.core.COUNTERS


def test_retention_op_counts_what_it_traces():
    q, k, v, a = retention_inputs(1, 20, 2.0)
    before = (telemetry.counter("power_retention_traced"),
              telemetry.counter("power_retention_chunks"))
    out = mx.nd.contrib.PowerRetention(*(mx.nd.array(np.asarray(x))
                                         for x in (q, k, v, a)), chunk=8)
    assert telemetry.counter("power_retention_traced") == before[0] + 1
    assert telemetry.counter("power_retention_chunks") == before[1] + 3
    np.testing.assert_allclose(out.asnumpy(),
                               np.asarray(retention_quadratic(q, k, v, a)),
                               rtol=2e-4, atol=2e-6)
    with pytest.raises(Exception, match="degree"):
        mx.nd.contrib.PowerRetention(*(mx.nd.array(np.asarray(x))
                                       for x in (q, k, v, a)), degree=3)


@pytest.mark.parametrize("tokens,block", [(32, 8), (30, 8), (5, 16)])
def test_blocked_head_equals_log_softmax_and_gather(tokens, block):
    rng = np.random.RandomState(tokens)
    h = jnp.asarray(rng.randn(2, tokens, 12), jnp.float32)
    w = jnp.asarray(rng.randn(17, 12), jnp.float32)
    y = jnp.asarray(rng.randint(0, 17, (2, tokens)), jnp.float32)

    def plain(h, w):
        logp = jax.nn.log_softmax(h @ w.T, axis=-1)
        return -jnp.take_along_axis(
            logp, y.astype(jnp.int32)[..., None], axis=-1).mean()

    want, grads = jax.value_and_grad(plain, (0, 1))(h, w)
    got, got_grads = jax.value_and_grad(
        lambda h, w: blocked_softmax_ce(h, w, y, block)[0], (0, 1))(h, w)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g, wnt in zip(got_grads, grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(wnt),
                                   rtol=1e-4, atol=1e-6)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def two_loop_softmax_ce(data, weight, label, block):
    """The head as it was before its forward rule formed the gradients
    (PR 31): the value's scan forward, a second scan that computes the
    logits again backward.  The yardstick of the fused rule: for a
    cotangent of 1 the two are the same arithmetic in the same order."""
    return lm._blocked_ce_value(data, weight, label, block)


def _two_loop_fwd(data, weight, label, block):
    return lm._blocked_ce_value(data, weight, label, block), \
        (data, weight, label)


def _two_loop_bwd(block, res, g):
    data, weight, label = res
    hs, ys, count = lm._head_blocks(data, label, block)
    scale = g.reshape(()).astype(jnp.float32) / count

    def body(dw, xs):
        h, y = xs
        logits = lm._block_logits(h, weight)
        p = jax.nn.softmax(logits, axis=-1)
        hit = jax.lax.broadcasted_iota(jnp.int32, p.shape, 1) == y[:, None]
        dlogits = ((p - hit) * jnp.where(y >= 0, scale, 0.0)[:, None]) \
            .astype(h.dtype)
        dh = jnp.dot(dlogits, weight, preferred_element_type=jnp.float32)
        dw = dw + jax.lax.dot_general(
            dlogits, h, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dw, dh.astype(h.dtype)

    dw, dh = jax.lax.scan(body, jnp.zeros(weight.shape, jnp.float32),
                          (hs, ys))
    dh = dh.reshape(-1, dh.shape[-1])[:count].reshape(data.shape)
    return dh, dw.astype(weight.dtype), jnp.zeros_like(label)


two_loop_softmax_ce.defvjp(_two_loop_fwd, _two_loop_bwd)


def head_inputs(tokens, dtype, classes=17, hidden=12):
    """An embedding / head weight, token ids and labels with two -1."""
    rng = np.random.RandomState(tokens)
    w = jnp.asarray(rng.randn(classes, hidden) * 0.5, dtype)
    ids = jnp.asarray(rng.randint(0, classes, (2, tokens)))
    h = jnp.asarray(rng.randn(2, tokens, hidden), dtype)
    y = rng.randint(0, classes, (2, tokens))
    y[0, 1] = y[1, tokens - 1] = -1
    return h, w, ids, jnp.asarray(y, jnp.float32)


@pytest.mark.parametrize("g", [1.0, 0.5, 3.0])
@pytest.mark.parametrize("tied", [False, True], ids=["free", "tied"])
@pytest.mark.parametrize("tokens,block", [(32, 8), (27, 8)],
                         ids=["whole", "ragged"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_fused_head_gradients(dtype, tokens, block, tied, g):
    """Loss, dh and dW of the rule that forms them in the forward scan:
    against log_softmax + gather for every cotangent, and to the bit
    against the two-loop rule for a cotangent of 1.  ``tied``: the head's
    weight is also the embedding that made the hidden states, so its
    gradient is the sum of both uses."""
    h, w, ids, y = head_inputs(tokens, dtype)

    def hidden(h, w):
        return jnp.tanh(w[ids]) if tied else h

    def plain(h, w):
        x, wf = hidden(h, w).astype(jnp.float32), w.astype(jnp.float32)
        logp = jax.nn.log_softmax(jnp.einsum(
            "bth,ch->btc", x, wf, precision="highest"), axis=-1)
        nll = -jnp.take_along_axis(
            logp, jnp.maximum(y, 0).astype(jnp.int32)[..., None],
            axis=-1)[..., 0]
        return g * jnp.where(y >= 0, nll, 0.0).sum() / y.size

    def through(op):
        return jax.jit(jax.value_and_grad(
            lambda h, w: g * op(hidden(h, w), w, y, block)[0], (0, 1)))(h, w)

    want, want_grads = jax.value_and_grad(plain, (0, 1))(h, w)
    got, got_grads = through(blocked_softmax_ce)
    low = dtype == jnp.bfloat16
    assert float(got) == pytest.approx(float(want), rel=2e-2 if low else 1e-5)
    for have, wnt in zip(got_grads, want_grads):
        assert have.dtype == dtype
        if tied and have.shape == h.shape:
            assert not np.asarray(have, np.float32).any()
            continue
        have, wnt = np.asarray(have, np.float32), np.asarray(wnt, np.float32)
        if low:     # dlogits are rounded to bf16 before the two products
            assert np.abs(have - wnt).max() <= 3e-2 * np.abs(wnt).max()
        else:
            np.testing.assert_allclose(have, wnt, rtol=1e-4, atol=1e-6)
    if g == 1.0:
        old, old_grads = through(two_loop_softmax_ce)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(old))
        for have, wnt in zip(got_grads, old_grads):
            np.testing.assert_array_equal(np.asarray(have, np.float32),
                                          np.asarray(wnt, np.float32))


def primitive_counts(jaxpr, counts=None, avals=None):
    """How often each primitive occurs in *jaxpr* and every jaxpr under
    it, and the abstract value of every variable they bind."""
    counts = {} if counts is None else counts
    avals = [] if avals is None else avals
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] = counts.get(eqn.primitive.name, 0) + 1
        avals.extend(v.aval for v in eqn.outvars)
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                primitive_counts(inner, counts, avals)
    return counts, avals


def test_head_makes_three_products_in_one_scan_under_differentiation():
    h, w, _, y = head_inputs(32, jnp.float32)

    def loss(h, w):
        return blocked_softmax_ce(h, w, y, 8)[0]

    counts, _ = primitive_counts(
        jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(h, w).jaxpr)
    assert counts["dot_general"] == 3 and counts["scan"] == 1
    old, _ = primitive_counts(jax.make_jaxpr(jax.value_and_grad(
        lambda h, w: two_loop_softmax_ce(h, w, y, 8)[0], (0, 1)))(h, w).jaxpr)
    assert old["dot_general"] == 4 and old["scan"] == 2
    # the value alone: one product a block, nothing of the weight's shape
    counts, avals = primitive_counts(jax.make_jaxpr(loss)(h, w).jaxpr)
    assert counts["dot_general"] == 1 and counts["scan"] == 1
    assert not [a for a in avals if getattr(a, "shape", None) == w.shape]


def stage(remat, k):
    """The scope of recomputation segment *k*, or no scope."""
    return mx.AttrScope(force_mirroring="True", mirror_stage=str(k)) \
        if remat else mx.AttrScope()


def head_symbol(remat=False):
    """A projection and the head on it, each in a stage of its own when
    *remat*: the head's op sits inside the last recomputation segment."""
    x = mx.sym.Variable("data")
    with stage(remat, 0):
        h = mx.sym.FullyConnected(x, num_hidden=16, flatten=False, name="fc0")
        h = mx.sym.Activation(h, act_type="tanh")
    with stage(remat, 1):
        h = mx.sym.FullyConnected(h, num_hidden=12, flatten=False, name="fc1")
        return mx.sym.contrib.BlockedSoftmaxCE(h, num_hidden=17, block=8,
                                               name="head")


def bound_head(sym):
    """``bound`` at [2, 15, 8] with labels in -1..16."""
    ex, grads = bound(sym, seed=3, data_shape=(2, 15, 8))
    ex.arg_dict["head_label"][:] = np.random.RandomState(3).randint(
        -1, 17, (2, 15)).astype(np.float32)
    return ex, grads


def test_fused_head_counter_counts_forward_rules_only():
    h, w, _, y = head_inputs(32, jnp.float32)
    before = telemetry.counter("lm_head_fused_traced")
    jax.grad(lambda h: blocked_softmax_ce(h, w, y, 8)[0])(h)
    assert telemetry.counter("lm_head_fused_traced") == before + 1
    ex, _ = bound_head(head_symbol())
    want = ex.forward(is_train=False)[0].asnumpy()
    assert telemetry.counter("lm_head_fused_traced") == before + 1
    ex.forward_backward()
    assert telemetry.counter("lm_head_fused_traced") > before + 1
    np.testing.assert_allclose(ex.outputs[0].asnumpy(), want, rtol=1e-6)


def test_fused_head_inside_a_recomputation_segment():
    plain, plain_grads = bound_head(head_symbol(False))
    marked, marked_grads = bound_head(head_symbol(True))
    for ex in (plain, marked):
        ex.forward_backward()
    np.testing.assert_array_equal(plain.outputs[0].asnumpy(),
                                  marked.outputs[0].asnumpy())
    assert np.abs(plain_grads["fc0_weight"].asnumpy()).max() > 0
    for name in plain_grads:
        np.testing.assert_allclose(plain_grads[name].asnumpy(),
                                   marked_grads[name].asnumpy(), rtol=1e-5,
                                   atol=1e-7)
    # a run-time cotangent scales what the forward formed
    marked.forward(is_train=True)
    marked.backward(out_grads=[mx.nd.array(np.array([0.5], np.float32))])
    for name in plain_grads:
        np.testing.assert_allclose(marked_grads[name].asnumpy(),
                                   0.5 * plain_grads[name].asnumpy(),
                                   rtol=1e-5, atol=1e-7)


def test_rms_norm_and_rotary_ops():
    rng = np.random.RandomState(0)
    x = rng.randn(2, 5, 3, 8).astype(np.float32)
    gamma = rng.rand(8).astype(np.float32) + 0.5
    got = mx.nd.RMSNorm(mx.nd.array(x), mx.nd.array(gamma), eps=1e-6)
    want = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * gamma
    np.testing.assert_allclose(got.asnumpy(), want, rtol=1e-5)
    # bf16 in: statistics in float32, result in bf16
    low = rms_norm(jnp.asarray(x, jnp.bfloat16), jnp.asarray(gamma,
                                                             jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(low, np.float32), want, rtol=3e-2,
                               atol=3e-2)
    rot = mx.nd.contrib.RotaryEmbedding(mx.nd.array(x), base=100.0).asnumpy()
    half = 4
    ang = np.arange(5)[:, None] * 100.0 ** (-np.arange(half) * 2.0 / 8)
    cos, sin = np.cos(ang)[None, :, None], np.sin(ang)[None, :, None]
    want = np.concatenate([x[..., :half] * cos - x[..., half:] * sin,
                           x[..., half:] * cos + x[..., :half] * sin], -1)
    np.testing.assert_allclose(rot, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(rot[:, 0], x[:, 0], rtol=1e-6)   # position 0
    # relative: (R_p q).(R_s k) depends on p - s only
    q = mx.nd.array(np.broadcast_to(x[:, :1], x.shape).copy())
    rq = mx.nd.contrib.RotaryEmbedding(q, base=100.0).asnumpy()
    assert np.allclose((rq[:, 1] * rq[:, 3]).sum(-1),
                       (rq[:, 2] * rq[:, 4]).sum(-1), atol=1e-4)


def test_transformer_rmsnorm_is_the_registered_implementation():
    from mxnet_tpu.models import transformer
    x = jnp.asarray(np.random.RandomState(1).randn(3, 16), jnp.float32)
    scale = jnp.linspace(0.5, 1.5, 16)
    np.testing.assert_array_equal(np.asarray(transformer._rmsnorm(x, scale)),
                                  np.asarray(rms_norm(x, scale)))


def test_symbol_infers_the_new_ops_parameters():
    data = mx.sym.Variable("data")
    net = mx.sym.RMSNorm(data, name="norm")
    net = mx.sym.contrib.BlockedSoftmaxCE(net, num_hidden=11, block=4,
                                          name="head")
    assert net.list_arguments() == ["data", "norm_gamma", "head_weight",
                                    "head_label"]
    args, outs, _ = net.infer_shape(data=(2, 6, 8))
    assert args == [(2, 6, 8), (8,), (11, 8), (2, 6)] and outs == [(1,)]


# --- recomputation segments -------------------------------------------------

def mlp(remat):
    """Two marked stages around an unmarked node; operators included."""
    x = mx.sym.Variable("data")
    with stage(remat, 0):
        h = mx.sym.FullyConnected(x, num_hidden=16, name="fc0")
        h = mx.sym.Activation(h, act_type="tanh") * 0.5 + h
    h = mx.sym.BatchNorm(h, name="bn")
    with stage(remat, 1):
        h = mx.sym.FullyConnected(h, num_hidden=4, name="fc1")
        h = -mx.sym.Activation(-h, act_type="softrelu")
    return mx.sym.sum(h * h)


def bound(sym, seed=0, data_shape=(5, 8)):
    rng = np.random.RandomState(seed)
    shapes, _, aux_shapes = sym.infer_shape(data=data_shape)
    args = {n: mx.nd.array(rng.randn(*s).astype(np.float32) * 0.3)
            for n, s in zip(sym.list_arguments(), shapes)}
    aux = {n: mx.nd.array(np.ones(s, np.float32))
           for n, s in zip(sym.list_auxiliary_states(), aux_shapes)}
    grads = {n: mx.nd.zeros(a.shape) for n, a in args.items()}
    return sym.bind(mx.cpu(), args, args_grad=grads, aux_states=aux), grads


def test_remat_segments_are_bit_identical_and_counted():
    before = telemetry.counter("executor_remat_segments")
    plain, plain_grads = bound(mlp(False))
    assert telemetry.counter("executor_remat_segments") == before
    marked, marked_grads = bound(mlp(True))
    assert telemetry.counter("executor_remat_segments") == before + 2
    # op by op (no fusion to differ in), the two graphs are the same
    # arithmetic bit for bit; compiled, XLA fuses the recomputed forward
    # differently and the last bit of a gradient may move
    def run(ex):
        args = [a._data for a in ex.arg_arrays]
        aux = [a._data for a in ex.aux_arrays]
        outs, vjp, new_aux = jax.vjp(
            lambda a: ex._train_fn(a, aux, jax.random.PRNGKey(0)), args,
            has_aux=True)
        return outs, vjp(tuple(jnp.ones_like(o) for o in outs))[0], new_aux

    with jax.disable_jit():
        eager = [run(ex) for ex in (plain, marked)]
    for a, b in zip(jax.tree_util.tree_leaves(eager[0]),
                    jax.tree_util.tree_leaves(eager[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for ex in (plain, marked):
        ex.forward_backward()
    np.testing.assert_array_equal(plain.outputs[0].asnumpy(),
                                  marked.outputs[0].asnumpy())
    for name in plain_grads:
        np.testing.assert_allclose(plain_grads[name].asnumpy(),
                                   marked_grads[name].asnumpy(), rtol=1e-5,
                                   atol=1e-6)
    for name in plain.aux_dict:
        np.testing.assert_array_equal(plain.aux_dict[name].asnumpy(),
                                      marked.aux_dict[name].asnumpy())
    # the train program recomputes (a checkpoint per segment, named); the
    # inference program is the plain walk
    specs = ([jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in marked.arg_arrays],
             [jax.ShapeDtypeStruct(a.shape, a.dtype)
              for a in marked.aux_arrays], jax.random.PRNGKey(0))
    train = str(jax.make_jaxpr(marked._train_fn)(*specs))
    assert train.count("prevent_cse=True") == 2
    lowered = jax.jit(marked._train_fn).lower(*specs)
    assert "remat_segment_1" in lowered.as_text(debug_info=True)
    evaluate = str(jax.make_jaxpr(
        _build_graph_fn(mlp(True), train_mode=False)[0])(*specs))
    assert "prevent_cse" not in evaluate


def parent_graph_fn(symbol, train_mode):
    """The graph walk as it was before recomputation segments existed
    (PR 26's ``_build_graph_fn``), kept here as the yardstick: a graph
    without the attribute has to trace to the same program."""
    nodes = _topo(symbol._outputs)
    arg_nodes = [n for n in nodes if n.op is None and not n.is_aux]
    aux_nodes = [n for n in nodes if n.op is None and n.is_aux]
    aux_update_src = {}
    for node in nodes:
        if node.op is None or not node.op.aux_updates:
            continue
        for aux_in, out_idx in node.op.aux_updates.items():
            src, _ = node.inputs[aux_in]
            if src.op is None and src.is_aux:
                aux_update_src[id(src)] = (node, out_idx)

    def graph_fn(arg_vals, aux_vals, rng):
        env = {}
        for n, v in zip(arg_nodes, arg_vals):
            env[(id(n), 0)] = v
        for n, v in zip(aux_nodes, aux_vals):
            env[(id(n), 0)] = v
        for node in nodes:
            if node.op is None:
                continue
            ins = [env[(id(s), oi)] for s, oi in node.inputs]
            outs = node.op.traceable(node.attrs, train_mode=train_mode,
                                     rng=None)(*ins)
            for i, o in enumerate(outs):
                env[(id(node), i)] = o
        outputs = tuple(env[(id(n), oi)] for n, oi in symbol._outputs)
        new_aux = tuple(
            env[(id(aux_update_src[id(n)][0]), aux_update_src[id(n)][1])]
            if id(n) in aux_update_src else env[(id(n), 0)]
            for n in aux_nodes)
        return outputs, new_aux
    return graph_fn


@pytest.mark.parametrize("train_mode", [False, True])
def test_graph_without_the_attribute_traces_as_on_the_parent(train_mode):
    sym = mlp(False)
    ex, _ = bound(sym)
    specs = ([jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ex.arg_arrays],
             [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in ex.aux_arrays],
             jax.random.PRNGKey(0))
    ours = jax.make_jaxpr(_build_graph_fn(sym, train_mode)[0])(*specs)
    theirs = jax.make_jaxpr(parent_graph_fn(sym, train_mode))(*specs)
    assert str(ours) == str(theirs)


def test_segment_that_is_left_and_reentered_is_refused():
    x = mx.sym.Variable("data")
    with mx.AttrScope(force_mirroring="True", mirror_stage="a"):
        h = mx.sym.FullyConnected(x, num_hidden=4, name="fc0")
    outside = mx.sym.Activation(h, act_type="relu")
    with mx.AttrScope(force_mirroring="True", mirror_stage="a"):
        out = mx.sym.FullyConnected(outside, num_hidden=4, name="fc1")
    with pytest.raises(mx.base.MXNetError, match="not convex"):
        out.simple_bind(mx.cpu(), data=(2, 3))


def test_attr_scope_marks_operators_too():
    x = mx.sym.Variable("data")
    with mx.AttrScope(force_mirroring="True", mirror_stage="3"):
        y = -(x * 2.0 + x)
    attrs = y.attr_dict()
    marked = [n for n, a in attrs.items()
              if a.get("__mirror_stage__") == "3"]
    assert len(marked) == 3                 # the product, the sum, the sign
    assert y.attr("force_mirroring") == "True"
    # outside a scope nothing is marked, and a call's own attr wins
    z = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    assert "__force_mirroring__" not in z.attr_dict().get("fc", {})


def test_gradient_buffers_are_zeros_until_read_or_written():
    ex = mlp(False).simple_bind(mx.cpu(), data=(5, 8))
    grad = ex.grad_dict["fc0_weight"]
    assert grad._buf is None                 # nothing allocated at bind
    assert grad.shape == (16, 8) and grad.dtype == np.float32
    assert grad._buf is None
    assert float(np.abs(grad.asnumpy()).sum()) == 0.0   # reads as zeros
    rng = np.random.RandomState(0)
    for arr in ex.arg_arrays:
        arr[:] = rng.randn(*arr.shape).astype(np.float32)
    ex.forward_backward()
    assert float(np.abs(grad.asnumpy()).sum()) > 0.0
    ex.release_grads()
    assert grad._buf is None and float(np.abs(grad.asnumpy()).sum()) == 0.0
