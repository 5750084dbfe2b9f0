"""The set-up checks of a cell against its plain reference, by the rule in
``correct.py``.  The reference runs on the cell's own devices, with its
rows split over them exactly as the cell's batch is."""
import importlib

import numpy as np

from chipbench import correct
from chipbench.reference import layers as L


def reference(cfg):
    return importlib.import_module("chipbench.reference." + cfg["reference"])


def forward_fn(cfg, dtype, train):
    import jax
    import jax.numpy as jnp
    ref = reference(cfg)

    def fn(params, aux, x, y):
        logits, stats = ref.forward(cfg, params, aux, x,
                                    jnp.dtype(dtype), train)
        loss, probs = L.softmax_xent(logits, y)
        return loss / x.shape[0], probs, stats
    return jax.jit(fn)


def grads_fn(cfg, dtype):
    import jax
    import jax.numpy as jnp
    ref = reference(cfg)

    def loss_fn(params, aux, x, y):
        logits, _ = ref.forward(cfg, params, aux, x, jnp.dtype(dtype), True)
        return L.softmax_xent(logits, y)[0]     # summed, as the system's is

    def fn(params, aux, x, y):
        # parameters enter in the dtype they are held in, so the gradient
        # comes back in it too
        params = {k: v.astype(dtype) for k, v in params.items()}
        return jax.grad(loss_fn)(params, aux, x, y)
    return jax.jit(fn)


def mean_nll(probs, labels):
    p = np.asarray(probs, np.float64)
    picked = p[np.arange(len(labels)), np.asarray(labels).astype(int)]
    return float(-np.log(np.maximum(picked, 1e-300)).mean())


def forward_rows(env, params, aux, x, y, sys_probs):
    """Training-mode loss and probabilities of the system against the
    reference in float32 and in the configuration's dtype."""
    import jax
    with jax.default_matmul_precision("highest"):
        loss32, probs32, _ = forward_fn(env.cfg, "float32", True)(
            params, aux, x, y)
        loss_p, probs_p, _ = forward_fn(env.cfg, env.cfg["dtype"], True)(
            params, aux, x, y)
    return [correct.judge("loss", mean_nll(sys_probs, np.asarray(y)),
                          float(loss32), float(loss_p)),
            correct.judge("probs", sys_probs, np.asarray(probs32),
                          np.asarray(probs_p))]


def grad_rows(env, params, aux, x, y, sys_grads):
    import jax
    with jax.default_matmul_precision("highest"):
        g32 = grads_fn(env.cfg, "float32")(params, aux, x, y)
        g_p = grads_fn(env.cfg, env.cfg["dtype"])(params, aux, x, y)
    return [correct.judge("grad:" + name, sys_grads[name],
                          np.asarray(g32[name], np.float32),
                          np.asarray(g_p[name], np.float32))
            for name in sorted(sys_grads)]


def verdict(env, rows):
    """(ok, printed) — prints the deviations and the summary on earlier
    lines; the head's two tensors, the loss and the probabilities must be
    well-conditioned and pass."""
    known = [d["name"] for d in env.cfg.get("known_deviations", [])]
    must = [r["name"] for r in rows
            if r["name"].endswith(("loss", "probs", "dense0_weight",
                                   "dense0_bias"))]
    ok, report = correct.summarise(rows, must, skip=known)
    env.say("deviations", {r["name"]: [float("%.3g" % r["dev_sys"]),
                                       float("%.3g" % r["dev_plain"]),
                                       r["verdict"]] for r in rows})
    env.say("check", report)
    return ok
