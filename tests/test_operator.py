"""Operator correctness via the numeric-gradient oracle + numpy references
(reference tests/python/unittest/test_operator.py doctrine, SURVEY §4)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import (assert_almost_equal, check_numeric_gradient,
                                  check_symbolic_forward,
                                  check_symbolic_backward, rand_ndarray)


# ---- elementwise unary: forward vs numpy + numeric gradient ---------------
UNARY_CASES = [
    ("relu", lambda x: np.maximum(x, 0), (-2, 2)),
    ("sigmoid", lambda x: 1 / (1 + np.exp(-x)), (-4, 4)),
    ("tanh", np.tanh, (-2, 2)),
    ("exp", np.exp, (-2, 2)),
    ("log", np.log, (0.1, 4)),
    ("sqrt", np.sqrt, (0.1, 4)),
    ("square", np.square, (-2, 2)),
    ("abs", np.abs, (0.3, 2)),
    ("sin", np.sin, (-3, 3)),
    ("cos", np.cos, (-3, 3)),
    ("arctan", np.arctan, (-2, 2)),
    ("cbrt", np.cbrt, (0.1, 4)),
    ("log1p", np.log1p, (-0.5, 3)),
    ("expm1", np.expm1, (-2, 2)),
    ("rsqrt", lambda x: 1 / np.sqrt(x), (0.5, 4)),
    ("reciprocal", lambda x: 1 / x, (0.5, 4)),
]


@pytest.mark.parametrize("name,ref,rng", UNARY_CASES,
                         ids=[c[0] for c in UNARY_CASES])
def test_unary_forward_and_grad(name, ref, rng):
    x = np.random.uniform(rng[0], rng[1], (3, 4)).astype(np.float32)
    fn = getattr(nd, name)
    out = fn(nd.array(x)).asnumpy()
    assert_almost_equal(out, ref(x).astype(np.float32), rtol=1e-4, atol=1e-5)
    check_numeric_gradient(lambda a: fn(a), [x], rtol=5e-2)


# ---- binary broadcast ------------------------------------------------------
BIN_CASES = [
    ("broadcast_add", np.add),
    ("broadcast_sub", np.subtract),
    ("broadcast_mul", np.multiply),
    ("broadcast_div", np.divide),
    ("broadcast_maximum", np.maximum),
    ("broadcast_minimum", np.minimum),
    ("broadcast_power", np.power),
]


@pytest.mark.parametrize("name,ref", BIN_CASES, ids=[c[0] for c in BIN_CASES])
def test_binary_broadcast(name, ref):
    a = np.random.uniform(0.5, 2, (2, 1, 4)).astype(np.float32)
    b = np.random.uniform(0.5, 2, (1, 3, 4)).astype(np.float32)
    fn = getattr(nd, name)
    assert_almost_equal(fn(nd.array(a), nd.array(b)).asnumpy(), ref(a, b),
                        rtol=1e-4, atol=1e-5)
    check_numeric_gradient(lambda x, y: fn(x, y), [a, b], rtol=5e-2)


# ---- reductions ------------------------------------------------------------
def test_reductions():
    x = np.random.uniform(-2, 2, (3, 4, 5)).astype(np.float32)
    for name, ref in [("sum", np.sum), ("mean", np.mean),
                      ("max", np.max), ("min", np.min),
                      ("prod", np.prod)]:
        fn = getattr(nd, name)
        assert_almost_equal(fn(nd.array(x)).asnumpy(), ref(x), rtol=1e-3)
        assert_almost_equal(fn(nd.array(x), axis=1).asnumpy(),
                            ref(x, axis=1), rtol=1e-3)
    check_numeric_gradient(lambda a: nd.sum(a, axis=1), [x], rtol=5e-2)
    assert_almost_equal(nd.argmax(nd.array(x), axis=1).asnumpy(),
                        np.argmax(x, axis=1))
    assert_almost_equal(nd.argmin(nd.array(x), axis=2).asnumpy(),
                        np.argmin(x, axis=2))


# ---- matrix / indexing -----------------------------------------------------
def test_dot_and_batch_dot():
    a = np.random.randn(4, 5).astype(np.float32)
    b = np.random.randn(5, 3).astype(np.float32)
    assert_almost_equal(nd.dot(nd.array(a), nd.array(b)).asnumpy(), a @ b,
                        rtol=1e-4)
    check_numeric_gradient(lambda x, y: nd.dot(x, y), [a, b], rtol=5e-2)
    ba = np.random.randn(2, 4, 5).astype(np.float32)
    bb = np.random.randn(2, 5, 3).astype(np.float32)
    assert_almost_equal(nd.batch_dot(nd.array(ba), nd.array(bb)).asnumpy(),
                        ba @ bb, rtol=1e-4)


def test_transpose_reshape_slice():
    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    assert_almost_equal(nd.transpose(nd.array(x)).asnumpy(), x.T)
    assert_almost_equal(
        nd.transpose(nd.array(x), axes=(1, 0, 2)).asnumpy(),
        x.transpose(1, 0, 2))
    assert_almost_equal(nd.reshape(nd.array(x), shape=(4, 6)).asnumpy(),
                        x.reshape(4, 6))
    assert_almost_equal(
        nd.slice_axis(nd.array(x), axis=1, begin=1, end=3).asnumpy(),
        x[:, 1:3])
    assert_almost_equal(nd.flip(nd.array(x), axis=1).asnumpy(),
                        x[:, ::-1])


def test_take_one_hot_pick_where():
    x = np.random.randn(5, 3).astype(np.float32)
    idx = np.array([0, 3, 1], dtype=np.float32)
    assert_almost_equal(nd.take(nd.array(x), nd.array(idx)).asnumpy(),
                        x[idx.astype(int)])
    oh = nd.one_hot(nd.array(idx), depth=5).asnumpy()
    assert_almost_equal(oh, np.eye(5, dtype=np.float32)[idx.astype(int)])
    p = nd.pick(nd.array(x), nd.array(np.array([0, 1, 2, 0, 1],
                                               dtype=np.float32)), axis=1)
    assert_almost_equal(p.asnumpy(), x[np.arange(5), [0, 1, 2, 0, 1]])
    cond = np.array([[1, 0, 1], [0, 1, 0]], dtype=np.float32)
    a = np.ones((2, 3), np.float32)
    b = np.zeros((2, 3), np.float32)
    assert_almost_equal(
        nd.where(nd.array(cond), nd.array(a), nd.array(b)).asnumpy(), cond)


def test_topk_sort_argsort():
    x = np.random.randn(3, 6).astype(np.float32)
    out = nd.topk(nd.array(x), k=2, axis=1).asnumpy()
    ref = np.argsort(-x, axis=1)[:, :2]
    assert_almost_equal(out, ref.astype(np.float32))
    assert_almost_equal(nd.sort(nd.array(x), axis=1).asnumpy(),
                        np.sort(x, axis=1))
    assert_almost_equal(nd.argsort(nd.array(x), axis=1).asnumpy(),
                        np.argsort(x, axis=1).astype(np.float32))


# ---- NN ops ----------------------------------------------------------------
def test_softmax_log_softmax():
    x = np.random.randn(4, 7).astype(np.float32)
    e = np.exp(x - x.max(axis=1, keepdims=True))
    ref = e / e.sum(axis=1, keepdims=True)
    assert_almost_equal(nd.softmax(nd.array(x)).asnumpy(), ref, rtol=1e-4)
    assert_almost_equal(nd.log_softmax(nd.array(x)).asnumpy(), np.log(ref),
                        rtol=1e-4)
    check_numeric_gradient(lambda a: nd.softmax(a), [x], rtol=5e-2)


def test_fully_connected_grad():
    x = np.random.randn(4, 6).astype(np.float32)
    w = np.random.randn(3, 6).astype(np.float32)
    b = np.random.randn(3).astype(np.float32)
    out = nd.FullyConnected(nd.array(x), nd.array(w), nd.array(b),
                            num_hidden=3).asnumpy()
    assert_almost_equal(out, x @ w.T + b, rtol=1e-4)
    check_numeric_gradient(
        lambda a, ww, bb: nd.FullyConnected(a, ww, bb, num_hidden=3),
        [x, w, b], rtol=5e-2)


def test_convolution_grad():
    x = np.random.randn(2, 3, 7, 7).astype(np.float32)
    w = np.random.randn(4, 3, 3, 3).astype(np.float32)
    b = np.random.randn(4).astype(np.float32)
    check_numeric_gradient(
        lambda a, ww, bb: nd.Convolution(a, ww, bb, kernel=(3, 3),
                                         num_filter=4, pad=(1, 1)),
        [x, w, b], rtol=5e-2, numeric_eps=1e-2)


def test_batchnorm_inference_matches_numpy():
    x = np.random.randn(4, 3, 5, 5).astype(np.float32)
    gamma = np.random.uniform(0.5, 1.5, 3).astype(np.float32)
    beta = np.random.randn(3).astype(np.float32)
    mean = np.random.randn(3).astype(np.float32)
    var = np.random.uniform(0.5, 1.5, 3).astype(np.float32)
    out = nd.BatchNorm(nd.array(x), nd.array(gamma), nd.array(beta),
                       nd.array(mean), nd.array(var), fix_gamma=False,
                       use_global_stats=True, eps=1e-5).asnumpy()
    ref = ((x - mean[None, :, None, None]) /
           np.sqrt(var[None, :, None, None] + 1e-5) *
           gamma[None, :, None, None] + beta[None, :, None, None])
    assert_almost_equal(out, ref, rtol=1e-3, atol=1e-4)


# ---- BatchNorm in training: one-pass moments, hand-derived VJP -------------
def _bn_reference(data, gamma, beta, mm, mv, eps=1e-3, momentum=0.9,
                  fix_gamma=True, axis=1, acc=None):
    """The two-pass formula in *acc* (float32 unless given), to be
    differentiated by jax.vjp: (out, new moving mean, new moving var)."""
    import jax.numpy as jnp
    from jax import lax
    acc = acc or jnp.float32
    ax = axis % data.ndim
    red = tuple(i for i in range(data.ndim) if i != ax)
    shape = [1] * data.ndim
    shape[ax] = data.shape[ax]
    x = data.astype(acc)
    g = jnp.ones(gamma.shape, acc) if fix_gamma else gamma.astype(acc)
    mean = jnp.mean(x, axis=red)
    var = jnp.mean(jnp.square(x - mean.reshape(shape)), axis=red)
    out = (x - mean.reshape(shape)) * lax.rsqrt(var + eps).reshape(shape) \
        * g.reshape(shape) + beta.astype(acc).reshape(shape)
    return out, mm.astype(acc) * momentum + mean * (1 - momentum), \
        mv.astype(acc) * momentum + var * (1 - momentum)


def _bn_inputs(shape, axis, dtype, offset=0.0, seed=0):
    import jax.numpy as jnp
    r = np.random.RandomState(seed)
    c = shape[axis]
    raw = [r.randn(*shape) + offset, r.uniform(0.5, 1.5, c), r.randn(c),
           r.randn(c), r.uniform(0.5, 1.5, c), r.randn(*shape)]
    return [jnp.asarray(a, jnp.float32).astype(dtype) for a in raw]


def _bn_value_and_grads(fn, x, gamma, beta, dy):
    """(out, new_mm, new_mv, dx, dgamma, dbeta) as float64 numpy."""
    import jax
    import jax.numpy as jnp
    (out, mm, mv), vjp = jax.vjp(fn, x, gamma, beta)
    grads = vjp((dy.astype(out.dtype), jnp.zeros_like(mm),
                 jnp.zeros_like(mv)))
    return [np.asarray(v, np.float64) for v in (out, mm, mv) + tuple(grads)]


def _bn_close(got, want, tol, names=("out", "moving_mean", "moving_var",
                                     "dx", "dgamma", "dbeta")):
    for name, g, w in zip(names, got, want):
        scale = max(float(np.abs(w).max()), 1e-6)
        assert float(np.abs(g - w).max()) <= tol * scale, \
            (name, float(np.abs(g - w).max()) / scale)


BN_SHAPES = {"2d": (7, 5), "4d": (4, 3, 5, 7), "5d": (3, 5, 2, 3, 3)}
# float32 keeps the two-pass lines; bfloat16 and float16 take _bn_train,
# and are held to their own rounding of the float32 formula's results
BN_TOL = {"float32": 1e-5, "bfloat16": 1.6e-2, "float16": 2e-3}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float16"])
@pytest.mark.parametrize("fix_gamma", [True, False])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("shape", sorted(BN_SHAPES))
def test_batchnorm_training_matches_two_pass(shape, axis, fix_gamma, dtype):
    from mxnet_tpu.ops.nn import _batch_norm
    x, gamma, beta, mm, mv, dy = _bn_inputs(BN_SHAPES[shape], axis, dtype)
    kw = dict(eps=1e-3, momentum=0.9, fix_gamma=fix_gamma, axis=axis)
    got = _bn_value_and_grads(
        lambda a, g, b: _batch_norm(a, g, b, mm, mv, train_mode=True, **kw),
        x, gamma, beta, dy)
    want = _bn_value_and_grads(
        lambda a, g, b: _bn_reference(a, g, b, mm, mv, **kw),
        x, gamma, beta, dy)
    _bn_close(got, want, BN_TOL[dtype])
    if fix_gamma:
        assert not got[4].any()


@pytest.mark.parametrize("offset", [0.0, 5.0, 50.0])
@pytest.mark.parametrize("axis", [1, -1])
def test_batchnorm_hand_vjp_is_the_two_pass_gradient(axis, offset):
    """_bn_train with float64 accumulators over float32 data (rounding out
    of the way) against jax.vjp of the two-pass formula in float64: the
    algebra of the one-pass moments and of the hand-derived VJP, also for
    a batch whose mean is 50 times its deviation."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _bn_train
    shape = (6, 5, 3, 7) if axis == 1 else (6, 3, 7, 5)
    x, gamma, beta, mm, mv, dy = _bn_inputs(shape, axis, "float32", offset)
    ax = axis % len(shape)

    def system(a, g, b):
        out, mean, var = _bn_train(a, g.astype(jnp.float64),
                                   b.astype(jnp.float64), ax, 1e-3)
        return out.astype(jnp.float64), \
            mm.astype(jnp.float64) * 0.9 + mean * 0.1, \
            mv.astype(jnp.float64) * 0.9 + var * 0.1
    got = _bn_value_and_grads(system, x, gamma, beta, dy)
    want = _bn_value_and_grads(
        lambda a, g, b: _bn_reference(a, g, b, mm, mv, fix_gamma=False,
                                      axis=axis, acc=jnp.float64),
        x, gamma, beta, dy)
    # out and dx leave in the data's float32
    _bn_close(got, want, 2e-7, names=("out", "", "", "dx"))
    _bn_close(got[1:3] + got[4:], want[1:3] + want[4:], 1e-9,
              names=("moving_mean", "moving_var", "dgamma", "dbeta"))


@pytest.mark.parametrize("dtype,offset,tol", [
    ("float32", 50.0, 1e-5),
    # bfloat16 steps by 0.25 at 50, a quarter of this batch's deviation;
    # what the float32 moments cancel on top of that is eps32 * 50^2 a
    # summand, within three roundings of the activation
    ("bfloat16", 50.0, 5e-2),
    ("bfloat16", 5.0, 1.6e-2)])
def test_batchnorm_training_far_from_zero(dtype, offset, tol):
    from mxnet_tpu.ops.nn import _batch_norm
    x, gamma, beta, mm, mv, dy = _bn_inputs((8, 16, 14, 14), 1, dtype,
                                            offset)
    kw = dict(eps=1e-3, momentum=0.9, fix_gamma=False, axis=1)
    got = _bn_value_and_grads(
        lambda a, g, b: _batch_norm(a, g, b, mm, mv, train_mode=True, **kw),
        x, gamma, beta, dy)
    want = _bn_value_and_grads(
        lambda a, g, b: _bn_reference(a, g, b, mm, mv, **kw),
        x, gamma, beta, dy)
    _bn_close(got, want, tol)


def _reduce_sums(jaxpr, channels):
    """Per-channel reduce_sum equations of a flat jaxpr with, for each,
    the set of those it depends on: [(eqn, {indices})]."""
    found, deps = [], {}
    for eqn in jaxpr.eqns:
        assert not any(hasattr(v, "jaxpr") or hasattr(v, "eqns")
                       for v in eqn.params.values()), \
            "nested jaxpr: %s" % eqn.primitive
        d = set()
        for v in eqn.invars:
            d |= deps.get(id(v), set())
        if eqn.primitive.name == "reduce_sum" and \
                eqn.outvars[0].aval.shape == (channels,):
            found.append((eqn, set(d)))
            d = d | {len(found) - 1}
        for v in eqn.outvars:
            deps[id(v)] = d
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
@pytest.mark.parametrize("axis", [1, -1])
def test_batchnorm_training_jaxpr_has_four_reductions_in_two_rounds(
        axis, dtype):
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.nn import _batch_norm
    shape = (4, 6, 5, 5) if axis == 1 else (4, 5, 5, 6)
    x, gamma, beta, mm, mv, dy = _bn_inputs(shape, axis, dtype)

    def fwd_bwd(a, g, b, m, v, ct):
        def visible_and_moving(a, g, b):
            out, new_mm, new_mv = _batch_norm(
                a, g, b, m, v, fix_gamma=False, axis=axis, train_mode=True)
            return out, (new_mm, new_mv)
        out, vjp, aux = jax.vjp(visible_and_moving, a, g, b, has_aux=True)
        return out, aux, vjp(ct)
    sums = _reduce_sums(jax.make_jaxpr(fwd_bwd)(x, gamma, beta, mm, mv,
                                                dy).jaxpr, 6)
    assert len(sums) == 4
    for eqn, _ in sums:
        assert eqn.invars[0].aval.dtype == jnp.float32
    # two rounds: the forward's pair are siblings, the backward's pair
    # are siblings and wait for nothing but the forward's
    assert [d for _, d in sums[:2]] == [set(), set()]
    assert all(d <= {0, 1} for _, d in sums[2:])


@pytest.mark.parametrize("mode", ["use_global_stats", "inference"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batchnorm_inference_jaxpr_is_the_parents(dtype, mode):
    """use_global_stats and train_mode=False trace to the lines BatchNorm
    had before the training branch changed (kept here as written then)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from mxnet_tpu import telemetry
    from mxnet_tpu.ops.nn import _batch_norm
    x, gamma, beta, mm, mv, _ = _bn_inputs((4, 3, 5, 5), 1, dtype)

    def parent(data, gamma, beta, moving_mean, moving_var, eps=1e-3,
               fix_gamma=True, axis=1):
        ax = axis % data.ndim
        shape = [1] * data.ndim
        shape[ax] = data.shape[ax]
        g = jnp.ones_like(gamma) if fix_gamma else gamma
        mean, var = moving_mean, moving_var
        inv = lax.rsqrt(var + eps)
        out = (data - mean.reshape(shape)) * inv.reshape(shape) \
            * g.reshape(shape) + beta.reshape(shape)
        return out, moving_mean, moving_var
    kw = {"use_global_stats": True, "train_mode": True} \
        if mode == "use_global_stats" else {"train_mode": False}
    before = telemetry.counter("batchnorm_onepass_traced")
    for fix_gamma in (True, False):
        got = jax.make_jaxpr(lambda *a: _batch_norm(
            *a, fix_gamma=fix_gamma, **kw))(x, gamma, beta, mm, mv)
        want = jax.make_jaxpr(lambda *a: parent(
            *a, fix_gamma=fix_gamma))(x, gamma, beta, mm, mv)
        assert str(got) == str(want)
    assert telemetry.counter("batchnorm_onepass_traced") == before


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batchnorm_training_batch_sharded_equals_one_device(dtype):
    """On the 8-device CPU mesh a batch-sharded input gives the
    single-device result: each reduction round is then an all-reduce."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.nn import _batch_norm
    devices = jax.devices()
    if len(devices) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    x, gamma, beta, mm, mv, dy = _bn_inputs((16, 6, 5, 5), 1, dtype)

    def fwd_bwd(a, g, b, ct):
        (out, new_mm, new_mv), vjp = jax.vjp(
            lambda a, g, b: _batch_norm(a, g, b, mm, mv, fix_gamma=False,
                                        train_mode=True), a, g, b)
        return (out, new_mm, new_mv) + vjp((ct, new_mm * 0, new_mv * 0))
    mesh = Mesh(np.array(devices[:8]), ("data",))
    rows, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    one = jax.jit(fwd_bwd)(x, gamma, beta, dy)
    many = jax.jit(fwd_bwd, in_shardings=(rows, rep, rep, rows))(
        x, gamma, beta, dy)
    assert len(many[0].sharding.device_set) == 8
    _bn_close([np.asarray(v, np.float64) for v in many],
              [np.asarray(v, np.float64) for v in one],
              1e-5 if dtype == "float32" else 8e-3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batchnorm_gluon_eager_tape_equals_hybridized(dtype):
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import nn as gnn
    r = np.random.RandomState(1)
    xv = r.randn(4, 3, 5, 5).astype(np.float32)
    wv = r.randn(4, 3, 5, 5).astype(np.float32)
    found = []
    for hybridize in (False, True):
        bn = gnn.BatchNorm(in_channels=3)
        bn.initialize()
        bn.gamma.set_data(mx.nd.array([0.5, 1.0, 1.5]))
        bn.beta.set_data(mx.nd.array([0.1, -0.2, 0.3]))
        bn.cast(dtype)
        if hybridize:
            bn.hybridize()
        x = mx.nd.array(xv).astype(dtype)
        x.attach_grad()
        with autograd.record():
            loss = (bn(x) * mx.nd.array(wv).astype(dtype)).sum()
        loss.backward()
        found.append([v.asnumpy().astype(np.float64) for v in (
            x.grad, bn.gamma.grad(), bn.beta.grad(),
            bn.running_mean.data(), bn.running_var.data())])
    _bn_close(found[1], found[0], 1e-5 if dtype == "float32" else 8e-3,
              names=("dx", "dgamma", "dbeta", "running_mean",
                     "running_var"))
    assert np.abs(found[0][0]).max() > 0 and np.abs(found[0][1]).max() > 0


@pytest.mark.parametrize("net,layers", [("resnet50_v1", 53),
                                        ("mobilenet1.0", 27)])
def test_batchnorm_onepass_counter_counts_training_traces(net, layers):
    """batchnorm_onepass_traced: one a BatchNorm a trace of a bfloat16
    training graph, none in inference and none in float32."""
    import jax
    from mxnet_tpu import symbol as S, telemetry
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import DataBatch, DataDesc

    def bound(dtype, for_training):
        zoo = vision.get_model(net, classes=10)
        zoo.cast(dtype)
        out = S.Cast(zoo(S.Cast(S.Variable("data"), dtype=dtype)),
                     dtype="float32")
        sym = S.SoftmaxOutput(out, S.Variable("softmax_label"),
                              name="softmax")
        mod = mx.mod.Module(sym, context=mx.cpu())
        mod.bind(data_shapes=[DataDesc("data", (2, 3, 32, 32))],
                 label_shapes=[DataDesc("softmax_label", (2,))],
                 for_training=for_training)
        mod.init_params(initializer=mx.initializer.Xavier())
        return mod

    def count():
        return telemetry.counter("batchnorm_onepass_traced")

    def trace(mod, fn):
        ex = mod._exec_group.execs[0]
        args = [ex.arg_dict[n]._data for n in ex.arg_names]
        aux = [ex.aux_dict[n]._data for n in ex.aux_names]
        # a new callable each time: make_jaxpr remembers a function's trace
        jax.make_jaxpr(lambda *a: fn(ex)(*a))(args, aux,
                                              jax.random.PRNGKey(0))

    mod = bound("bfloat16", True)
    before = count()
    trace(mod, lambda ex: ex._train_fn)
    assert count() - before == layers
    trace(mod, lambda ex: ex._train_fn)
    assert count() - before == 2 * layers
    if net == "mobilenet1.0":
        # a bind and a step: however many times the step is traced, whole
        # graphs at a time
        mid = count()
        mod.init_optimizer(optimizer="sgd")
        mod._fit_step(DataBatch([mx.nd.zeros((2, 3, 32, 32))],
                                [mx.nd.zeros((2,))]))
        assert count() > mid and (count() - mid) % layers == 0
    before = count()
    trace(mod, lambda ex: ex._eval_jit)
    trace(bound("float32", True), lambda ex: ex._train_fn)
    assert count() == before


def test_batchnorm_onepass_counter_stays_for_a_graph_without_batchnorm():
    """The Brumby training graph (RMSNorm, no BatchNorm) traced in
    bfloat16: the counter does not move."""
    import jax
    from mxnet_tpu import telemetry
    from mxnet_tpu.io import DataDesc
    from mxnet_tpu.models.brumby import BRUMBY_TINY, brumby_symbol
    mod = mx.mod.Module(brumby_symbol(dict(BRUMBY_TINY, dtype="bfloat16")),
                        context=mx.cpu())
    mod.bind(data_shapes=[DataDesc("data", (1, 16))],
             label_shapes=[DataDesc("softmax_label", (1, 16))])
    mod.init_params(initializer=mx.initializer.Xavier())
    ex = mod._exec_group.execs[0]
    before = telemetry.counter("batchnorm_onepass_traced")
    jaxpr = jax.make_jaxpr(lambda *a: ex._train_fn(*a))(
        [ex.arg_dict[n]._data for n in ex.arg_names],
        [ex.aux_dict[n]._data for n in ex.aux_names], jax.random.PRNGKey(0))
    assert "remat" in str(jaxpr) or "checkpoint" in str(jaxpr)
    assert telemetry.counter("batchnorm_onepass_traced") == before


@pytest.mark.parametrize("model,dtype,head", [
    ("brumby", "float32", "lm_head_weight"),
    # XLA:CPU runs no bf16 product inside the retention op's chunk scan, so
    # the bf16 case is the sparse-expert toy, whose head is its embedding
    ("lfm2", "bfloat16", "embed_weight")])
def test_fit_on_the_fused_head_equals_fit_on_the_two_loop_head(
        monkeypatch, model, dtype, head):
    """Three batches of an LM toy through ``Module.fit``'s fused step:
    every loss and the updated head weight are, bit for bit, what the head
    gives whose backward computes the logits a second time
    (``tests/test_lm_ops.py`` keeps that rule as the yardstick)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.models.brumby import BRUMBY_TINY, brumby_symbol
    from mxnet_tpu.models.lfm2 import LFM2_MOE_TINY, lfm2_moe_symbol
    from mxnet_tpu.ops import lm
    from tests.test_lm_ops import two_loop_softmax_ce
    toy, symbol = {"brumby": (BRUMBY_TINY, brumby_symbol),
                   "lfm2": (LFM2_MOE_TINY, lfm2_moe_symbol)}[model]
    cfg = dict(toy, dtype=dtype)
    ids = np.random.RandomState(5).randint(
        0, cfg["vocab_size"], (6, 17)).astype(np.float32)

    def fit():
        mx.random.seed(11)
        it = mx.io.NDArrayIter(ids[:, :-1], ids[:, 1:], batch_size=2,
                               label_name="softmax_label")
        mod = mx.mod.Module(symbol(cfg), context=mx.cpu())
        losses = []
        mod.fit(it, eval_metric="loss", num_epoch=1,
                initializer=mx.initializer.Xavier(magnitude=2.0),
                optimizer="sgd",
                optimizer_params=(("learning_rate", 0.3), ("momentum", 0.9)),
                batch_end_callback=lambda p: losses.append(
                    p.eval_metric.get()[1]))
        return losses, mod.get_params()[0][head].asnumpy()

    count = lambda: telemetry.counter("lm_head_fused_traced")
    before = count()
    losses, weight = fit()
    fused = count()
    assert fused > before
    monkeypatch.setattr(lm, "blocked_softmax_ce", two_loop_softmax_ce)
    old_losses, old_weight = fit()
    assert count() == fused
    assert len(losses) == 3 and losses == old_losses
    assert losses[0] != losses[2]
    np.testing.assert_array_equal(weight.astype(np.float32),
                                  old_weight.astype(np.float32))


# ---- symbolic check helpers on ops ----------------------------------------
def test_check_symbolic_forward_backward():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    out = 2 * a + a * b
    av = np.random.randn(3, 4).astype(np.float32)
    bv = np.random.randn(3, 4).astype(np.float32)
    check_symbolic_forward(out, [av, bv], [2 * av + av * bv])
    og = np.ones((3, 4), np.float32)
    check_symbolic_backward(out, [av, bv], [og],
                            {"a": 2 + bv, "b": av})


def test_check_numeric_gradient_symbol_path():
    """The Symbol overload must produce real (non-zero) autograd grads."""
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    out = mx.sym.broadcast_mul(a, b) + a
    av = np.random.uniform(0.5, 1.5, (3, 4)).astype(np.float32)
    bv = np.random.uniform(0.5, 1.5, (3, 4)).astype(np.float32)
    check_numeric_gradient(out, {"a": av, "b": bv}, rtol=5e-2)
    check_numeric_gradient(out, {"a": av, "b": bv}, grad_nodes=["b"],
                           rtol=5e-2)


# ---- random ops ------------------------------------------------------------
def test_random_ops_statistics():
    mx.random.seed(7)
    u = nd.random.uniform(0, 1, shape=(20000,)).asnumpy()
    assert 0.48 < u.mean() < 0.52
    n = nd.random.normal(0, 1, shape=(20000,)).asnumpy()
    assert abs(n.mean()) < 0.03 and 0.95 < n.std() < 1.05
    p = nd.random.poisson(lam=4.0, shape=(20000,)).asnumpy()
    assert 3.8 < p.mean() < 4.2
    g = nd.random.gamma(alpha=3.0, beta=1.0, shape=(20000,)).asnumpy()
    assert 2.8 < g.mean() < 3.2


def test_clip_round_sign():
    x = np.random.uniform(-3, 3, (4, 5)).astype(np.float32)
    assert_almost_equal(nd.clip(nd.array(x), -1, 1).asnumpy(),
                        np.clip(x, -1, 1))
    assert_almost_equal(nd.sign(nd.array(x)).asnumpy(), np.sign(x))
    assert_almost_equal(nd.round(nd.array(x)).asnumpy(), np.round(x))
    assert_almost_equal(nd.floor(nd.array(x)).asnumpy(), np.floor(x))
    assert_almost_equal(nd.ceil(nd.array(x)).asnumpy(), np.ceil(x))
