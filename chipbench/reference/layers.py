"""Plain layers for the reference models: straight jax.numpy / lax, no
kernels, no fusion tricks, one ``dtype`` for everything between the float32
input and the float32 logits.  Written from the papers' equations; the only
thing taken from the system under test is its seeded weights, by name.

Layout is NCHW / OIHW, as the papers' tables read.
"""
import jax
import jax.numpy as jnp
from jax import lax

BN_EPS = 1e-5          # Ioffe & Szegedy's epsilon as both zoo nets set it


class Taker:
    """Hands out the system's tensors by name and remembers which were
    asked for, so that a reference written from memory cannot silently
    skip one (the zoo's ResNet keeps biases on its 1x1 convolutions)."""

    def __init__(self, tensors):
        self._tensors = tensors
        self._taken = set()

    def has(self, name):
        return name in self._tensors

    def __call__(self, name):
        self._taken.add(name)
        return self._tensors[name]

    def assert_all_taken(self):
        left = sorted(set(self._tensors) - self._taken)
        if left:
            raise AssertionError(
                "reference consumed %d of %d tensors; never asked for %s"
                % (len(self._taken), len(self._tensors), left[:6]))


def conv(x, w, b=None, stride=1, pad=0, groups=1):
    y = lax.conv_general_dilated(
        x, w, window_strides=(stride, stride),
        padding=((pad, pad), (pad, pad)), feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return y if b is None else y + b[None, :, None, None]


def batch_norm(x, gamma, beta, running, train):
    """Returns (y, (mean, var)): the statistics it normalised with — the
    batch's (biased variance) when training, the running ones otherwise."""
    if train:
        mean = jnp.mean(x, axis=(0, 2, 3))
        var = jnp.var(x, axis=(0, 2, 3))
    else:
        mean, var = running
    scale = gamma * lax.rsqrt(var + jnp.asarray(BN_EPS, x.dtype))
    y = (x - mean[None, :, None, None]) * scale[None, :, None, None] \
        + beta[None, :, None, None]
    return y, (mean, var)


def relu(x):
    return jnp.maximum(x, 0)


def max_pool(x, k, stride, pad):
    return lax.reduce_window(
        x, -jnp.inf, lax.max, (1, 1, k, k), (1, 1, stride, stride),
        ((0, 0), (0, 0), (pad, pad), (pad, pad)))


def global_avg_pool(x):
    return jnp.mean(x, axis=(2, 3))


def dense(x, w, b):
    return x @ w.T + b


def softmax_xent(logits, labels):
    """(summed cross-entropy, probabilities) from float32 logits."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return -jnp.sum(picked), jnp.exp(logp)


def sgd_momentum(w, g, mom, lr, momentum, wd, rescale):
    """mom' = momentum*mom - lr*(rescale*g + wd*w);  w' = w + mom'."""
    mom = momentum * mom - lr * (rescale * g + wd * w)
    return w + mom, mom


class Net:
    """Per-forward bookkeeping shared by the reference models: takes
    weights by name in ``dtype``, runs conv+BN units, collects each
    BatchNorm's statistics under the system's aux names."""

    def __init__(self, params, aux, dtype, train):
        self.take = Taker(params)
        self.aux = Taker(aux)
        self.dtype, self.train = dtype, train
        self.stats = {}

    def w(self, name):
        return self.take(name).astype(self.dtype)

    def conv(self, x, name, stride=1, pad=0, groups=1):
        bias = self.w(name + "_bias") if self.take.has(name + "_bias") \
            else None
        return conv(x, self.w(name + "_weight"), bias, stride, pad, groups)

    def bn(self, x, name):
        running = (self.aux(name + "_running_mean").astype(self.dtype),
                   self.aux(name + "_running_var").astype(self.dtype))
        y, (mean, var) = batch_norm(x, self.w(name + "_gamma"),
                                    self.w(name + "_beta"), running,
                                    self.train)
        self.stats[name + "_running_mean"] = mean
        self.stats[name + "_running_var"] = var
        return y

    def finish(self, logits):
        self.take.assert_all_taken()
        self.aux.assert_all_taken()
        return logits.astype(jnp.float32), self.stats


def model_prefix(params):
    """The zoo numbers its nets (``resnetv10_``, ``resnetv11_`` ...): the
    reference addresses tensors relative to that prefix."""
    heads = [k for k in params if k.endswith("dense0_weight")]
    if len(heads) != 1:
        raise AssertionError("expected one classifier head, found %s" % heads)
    return heads[0][:-len("dense0_weight")]
