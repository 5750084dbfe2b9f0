"""User-facing Pallas kernel registration (VERDICT r3 #5; RTC parity —
reference python/mxnet/rtc.py + src/common/rtc.cc:32-80)."""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import ndarray as nd
from mxnet_tpu import symbol as S


@pytest.fixture
def _cleanup():
    before = set(mx.pallas.registered_kernels())
    yield
    for name in list(mx.pallas.registered_kernels()):
        if name not in before:
            mx.pallas.unregister(name)


def _scale_body(x_ref, o_ref, *, alpha):
    o_ref[...] = x_ref[...] * alpha


def _register_scale(name="pl_scale", **kw):
    from jax.experimental import pallas as pl
    kw.setdefault("interpret", True)     # no TPU here: the explicit mode

    def pl_scale(x, alpha=2.0, interpret=False):
        return pl.pallas_call(
            functools.partial(_scale_body, alpha=float(alpha)),
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            interpret=bool(interpret))(x)

    return mx.pallas.register(
        name, pl_scale,
        grad=lambda og, ins, outs, attrs:
        (og[0] * float(attrs.get("alpha", 2.0)),), **kw)


def test_eager_and_symbolic_invocation(_cleanup):
    fn = _register_scale()
    x = nd.array(np.arange(6.0).reshape(2, 3))
    np.testing.assert_allclose(fn(x, alpha=3.0).asnumpy(),
                               x.asnumpy() * 3.0)
    # exposed on the nd namespace like a built-in
    np.testing.assert_allclose(nd.pl_scale(x, alpha=3.0).asnumpy(),
                               x.asnumpy() * 3.0)
    # symbolic: bind + forward
    s = S.pl_scale(S.Variable("d"), alpha=4.0)
    ex = s.simple_bind(mx.cpu(), grad_req="write", d=(2, 3))
    ex.arg_dict["d"][:] = x.asnumpy()
    out = ex.forward()[0]
    np.testing.assert_allclose(out.asnumpy(), x.asnumpy() * 4.0)


def test_semantic_grad_through_executor(_cleanup):
    _register_scale()
    s = S.sum(S.pl_scale(S.Variable("d"), alpha=5.0))
    ex = s.simple_bind(mx.cpu(), grad_req="write", d=(2, 3))
    ex.arg_dict["d"][:] = 1.0
    ex.forward(is_train=True)
    ex.backward()
    np.testing.assert_allclose(ex.grad_dict["d"].asnumpy(),
                               np.full((2, 3), 5.0))


def test_autograd_through_pure_jax_kernel(_cleanup):
    # a pure-JAX body needs no grad=: jax.vjp differentiates it
    mx.pallas.register("pl_cube", lambda x: x ** 3)
    x = nd.array(np.array([1.0, 2.0]))
    x.attach_grad()
    with mx.autograd.record():
        y = nd.pl_cube(x)
    y.backward(nd.array(np.ones(2)))
    np.testing.assert_allclose(x.grad.asnumpy(), 3 * x.asnumpy() ** 2)


def test_training_through_registered_kernel(_cleanup):
    """Train a tiny Module whose graph routes through the user kernel."""
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.module import Module
    _register_scale()
    net = S.FullyConnected(S.Variable("data"), num_hidden=4, name="fc_a")
    net = S.pl_scale(net, alpha=0.5)
    net = S.FullyConnected(net, num_hidden=2, name="fc_b")
    net = S.SoftmaxOutput(net, S.Variable("softmax_label"), name="softmax")

    rng = np.random.RandomState(0)
    X = rng.randn(16, 3).astype(np.float32)
    Y = (X.sum(axis=1) > 0).astype(np.float32)
    it = NDArrayIter(X, Y, batch_size=8, label_name="softmax_label")
    mod = Module(net, context=mx.cpu())
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.5),))
    w0 = mod._exec_group.execs[0].arg_dict["fc_a_weight"].asnumpy().copy()
    mod.fit(it, num_epoch=3)
    w1 = mod._exec_group.execs[0].arg_dict["fc_a_weight"].asnumpy()
    assert np.abs(w1 - w0).max() > 0, "no learning through the kernel"


def test_duplicate_name_rejected(_cleanup):
    _register_scale()
    with pytest.raises(mx.MXNetError):
        _register_scale()
    _register_scale(force=True)  # explicit replacement allowed
    assert mx.pallas.registered_kernels().count("pl_scale") == 1


def test_unregister_removes_wrappers(_cleanup):
    _register_scale("pl_gone")
    assert hasattr(nd, "pl_gone") and hasattr(S, "pl_gone")
    mx.pallas.unregister("pl_gone")
    assert not hasattr(nd, "pl_gone")
    assert not hasattr(S, "pl_gone")
    with pytest.raises(mx.MXNetError):
        mx.pallas.unregister("pl_gone")


def test_builtin_protected_from_unregister():
    with pytest.raises(mx.MXNetError):
        mx.pallas.unregister("Convolution")


def test_force_over_builtin_restored_on_unregister():
    """force=True over a built-in must stash the original op and restore
    it (registry + nd/sym wrappers) on unregister — r4 advice: deleting
    the built-in left the framework without a core operator."""
    from mxnet_tpu.ops.registry import OP_REGISTRY
    original = OP_REGISTRY["relu"]
    x = nd.array(np.array([-1.0, 2.0], np.float32))

    def fake_relu(a):
        return a * 0.0 + 7.0

    try:
        mx.pallas.register("relu", fake_relu, force=True)
        assert np.allclose(nd.relu(x).asnumpy(), 7.0)
    finally:
        mx.pallas.unregister("relu")
    assert OP_REGISTRY["relu"] is original
    assert np.allclose(nd.relu(x).asnumpy(), [0.0, 2.0])
    # double force-register then unregister still restores the ORIGINAL
    try:
        mx.pallas.register("relu", fake_relu, force=True)
        mx.pallas.register("relu", fake_relu, force=True)
    finally:
        mx.pallas.unregister("relu")
    assert OP_REGISTRY["relu"] is original


def _registered_fn(name):
    """The registered op as a pure function of one jax array."""
    from mxnet_tpu.ops.registry import OP_REGISTRY
    op = OP_REGISTRY[name]
    return lambda x, **attrs: op.apply([x], attrs, train_mode=False,
                                       rng=None)[0]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_registered_kernel_lowers_for_tpu(_cleanup, dtype):
    """The mx.pallas docstring kernel compiles with Mosaic under the
    package's x64 setting: lowered for TPU from this CPU host (the chip
    run of the same kernel is chip_smoke.py's kernels phase)."""
    assert jax.config.jax_enable_x64
    _register_scale("pl_scale_tpu", interpret=False)
    fn = _registered_fn("pl_scale_tpu")
    text = jax.jit(functools.partial(fn, alpha=3.0)).trace(
        jax.ShapeDtypeStruct((8, 128), dtype)).lower(
            lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_compiled_kernel_without_tpu_raises(_cleanup):
    """interpret=False is the default and is never swapped for the
    interpreter behind the caller's back."""
    _register_scale("pl_scale_compiled", interpret=False)
    with pytest.raises(Exception, match="[Ii]nterpret"):
        nd.pl_scale_compiled(nd.ones((8, 128)))
