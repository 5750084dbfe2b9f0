"""How the benchmark builds the system under test: the zoo net as a symbol,
a bound and seeded Module.  Constructions copied from ``chip_smoke.py``
(PR 21 proved them on the chip); copied, not imported, so that a later
change to the smoke cannot move the yardstick.
"""
import numpy as np


def fold_seed(seed):
    """--seed may exceed 32 signed bits; the system's seeders take 31."""
    return int(seed) % 2147483647


def zoo_net(cfg):
    from mxnet_tpu.gluon.model_zoo import vision
    return vision.get_model(cfg["zoo_model"], classes=cfg["classes"])


def train_symbol(net, dtype):
    """float32 in, float32 out: the casts are part of the graph, so the
    trainer, the checkpoint and the server all take float32 rows."""
    from mxnet_tpu import symbol as S
    net.cast(dtype)
    out = net(S.Cast(S.Variable("data"), dtype=dtype))
    out = S.Cast(out, dtype="float32")
    return S.SoftmaxOutput(out, S.Variable("softmax_label"), name="softmax")


def bind_module(mx, sym, ctx, batch, image, for_training=True):
    from mxnet_tpu.io import DataDesc
    mod = mx.mod.Module(sym, context=ctx)
    mod.bind(data_shapes=[DataDesc("data", (batch, 3, image, image),
                                   dtype=np.float32)],
             label_shapes=[DataDesc("softmax_label", (batch,),
                                    dtype=np.float32)],
             for_training=for_training)
    return mod


def optimizer_params(cfg):
    opt = cfg["optimizer"]
    return tuple((k, v) for k, v in opt.items() if k != "name")


def seeded_module(mx, cfg, sym, ctx, batch, seed):
    """Bound for training, Xavier weights from the seed, optimizer set."""
    mx.random.seed(fold_seed(seed))
    mod = bind_module(mx, sym, ctx, batch, cfg["image"])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer=cfg["optimizer"]["name"],
                       optimizer_params=optimizer_params(cfg))
    return mod


def host_params(mod):
    """(params, aux) as float32 numpy, by name: the reference's input."""
    return tuple({k: v.asnumpy().astype(np.float32) for k, v in d.items()}
                 for d in mod.get_params())
