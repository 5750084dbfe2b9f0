"""Each plain reference equals the system in float32 at toy size: the same
seeded weights, training-mode probabilities, every parameter gradient
through the Module's own backward, BatchNorm statistics and one SGD step."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon.model_zoo import vision

from chipbench import build
from chipbench.reference import layers as L
from chipbench.reference import mobilenet_v1, resnet50_v1

BATCH, IMAGE, CLASSES = 8, 32, 10
OPT = {"name": "sgd", "learning_rate": 0.01, "momentum": 0.9, "wd": 1e-4}


def toy_resnet():
    # the ResNet-50 structure (bottlenecks, projection shortcuts, a stage
    # of more than one block) at toy widths
    cfg = {"layers": [1, 2, 1, 1], "channels": [8, 16, 32, 64, 128],
           "classes": CLASSES, "image": IMAGE, "optimizer": OPT}
    net = vision.resnet.ResNetV1(vision.resnet.BottleneckV1, cfg["layers"],
                                 cfg["channels"], classes=CLASSES)
    return cfg, net, resnet50_v1


def toy_mobilenet():
    cfg = {"multiplier": 0.25, "classes": CLASSES, "image": IMAGE,
           "optimizer": OPT}
    return cfg, vision.get_model("mobilenet0.25", classes=CLASSES), \
        mobilenet_v1


@pytest.mark.parametrize("make", [toy_resnet, toy_mobilenet])
def test_reference_equals_system_in_float32(make):
    cfg, net, ref = make()
    sym = build.train_symbol(net, "float32")
    mod = build.seeded_module(mx, cfg, sym, mx.cpu(), BATCH, seed=7)
    params, aux = build.host_params(mod)
    rng = np.random.RandomState(3)
    x = rng.rand(BATCH, 3, IMAGE, IMAGE).astype(np.float32)
    y = rng.randint(0, CLASSES, (BATCH,)).astype(np.float32)

    def loss_fn(p):
        logits, stats = ref.forward(cfg, p, aux, jnp.asarray(x),
                                    jnp.float32, True)
        loss, probs = L.softmax_xent(logits, jnp.asarray(y))
        return loss, (probs, stats)

    with jax.default_matmul_precision("highest"):
        (_, (probs, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)

    batch = mx.io.DataBatch([mx.nd.array(x)], [mx.nd.array(y)])
    mod.forward_backward(batch)
    got = mod.get_outputs()[0].asnumpy()
    np.testing.assert_allclose(got, probs, rtol=1e-4, atol=1e-6)
    group = mod._exec_group
    assert set(group.param_names) == set(params)
    # a bias in front of a BatchNorm has a gradient of exactly zero (the
    # batch mean removes it): what either side holds there is rounding
    # noise, so a tensor's scale is floored at 1% of the largest gradient
    top = max(float(np.abs(g).max()) for g in grads.values())
    for name, per_dev in zip(group.param_names, group.grad_arrays):
        g = per_dev[0].asnumpy()
        scale = max(float(np.abs(grads[name]).max()), 1e-2 * top)
        assert np.abs(g - grads[name]).max() <= 1e-4 * scale, name

    # one optimizer step and the running statistics it leaves
    mod.update()
    new_params, new_aux = build.host_params(mod)
    for name in stats:
        want = 0.9 * aux[name] + 0.1 * np.asarray(stats[name])
        np.testing.assert_allclose(new_aux[name], want, rtol=1e-4,
                                   atol=1e-6, err_msg=name)
    for name in params:
        wd = OPT["wd"] if name.endswith(("_weight", "_gamma")) else 0.0
        want, _ = L.sgd_momentum(params[name], np.asarray(grads[name]), 0.0,
                                 OPT["learning_rate"], OPT["momentum"], wd,
                                 1.0 / BATCH)
        step = np.abs(np.asarray(want) - params[name]).max()
        assert np.abs(new_params[name] - want).max() <= \
            1e-4 * step + 1e-6, name     # a few float32 ulps at 1.0


def test_reference_refuses_an_unconsumed_tensor():
    cfg, net, ref = toy_mobilenet()
    sym = build.train_symbol(net, "float32")
    mod = build.seeded_module(mx, cfg, sym, mx.cpu(), BATCH, seed=1)
    params, aux = build.host_params(mod)
    params["mobilenet_stray_bias"] = np.zeros(3, np.float32)
    x = jnp.zeros((BATCH, 3, IMAGE, IMAGE), jnp.float32)
    with pytest.raises(AssertionError, match="never asked for"):
        ref.forward(cfg, params, aux, x, jnp.float32, True)
