"""ResNet-50 train + inference throughput, batch 32, bf16, on one TPU chip.

One process: it measures on the chip and prints ONE JSON line, or it
exits non-zero.  There is no CPU fallback, no probe child and no retry —
a chip belongs to one process, and a number taken on the host is not a
device number (``chip_smoke.py`` is the quick "does it start" proof; this
file is the longer measurement).

The primary series is the *training* step rate (fwd + bwd + SGD-momentum
update, one jitted donated XLA program) — the number the reference's own
headline tables report (``example/image-classification/README.md:255-260,
293-320``) — taken twice: on a raw jax step built from the framework's
pure optimizer core, and through the framework's own path (symbol ->
``Module`` -> ``CachedTrainStep``).  The line also carries inference
img/s and an MFU estimate.

Baselines (BASELINE.md, 1x K80):
 - inference resnet-50 bs32: 109 img/s (README.md:149-155)
 - training: the reference publishes resnet-152 bs32 at 20.08 img/s
   (README.md:309). Scaling by the fwd FLOP ratio (resnet-152 ~11.5 GMAC
   vs resnet-50 ~4.1 GMAC) gives a derived resnet-50 K80 training
   baseline of ~56.3 img/s, used for vs_baseline.

MFU: achieved FLOP/s over the chip's bf16 peak.  FLOPs per step are XLA's
own cost analysis of the compiled train step; the peak is
``telemetry.costs.PEAK_TABLE`` keyed by ``device_kind`` (the repo's one
peak table — an unknown kind is an error).
"""
import json
import sys
import time

import numpy as np

BATCH = 32
WINDOW_S = 5.0
INFER_BASELINE_IMG_S = 109.0
TRAIN_BASELINE_IMG_S = 56.3       # derived: 20.08 img/s (rn152) * 11.5/4.1


def _timed_rate(run, batch, target_s=WINDOW_S, max_iters=2000, repeats=3):
    """Median img/s over `repeats` windows of ~target_s each."""
    run()                                    # warmup / compile
    t0 = time.perf_counter()
    run()
    per_iter = max(time.perf_counter() - t0, 1e-5)
    iters = max(2, min(max_iters, int(target_s / per_iter)))
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            run()
        dt = time.perf_counter() - t0
        rates.append(batch * iters / dt)
    return float(np.median(rates)), iters


def _build_train_step(forward, params, device):
    """One fused train step using the framework's pure optimizer core."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import optimizer as opt_mod
    sgd = opt_mod.create("sgd", learning_rate=0.1, momentum=0.9, wd=1e-4,
                         rescale_grad=1.0)
    train_fwd = forward.train_forward
    hyper = {"lr": 0.1, "wd": 1e-4, "t": 1}

    def loss_fn(p, aux, x, y):
        logits, new_aux = train_fwd(p, aux, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, y[:, None], axis=1)
        return jnp.mean(nll), new_aux

    def step(p, m, aux, x, y):
        (loss, new_aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(p, aux, x, y)
        new_p, new_m = {}, {}
        for n in p:
            new_p[n], new_m[n] = sgd.update_step(p[n], grads[n], m[n], hyper)
        return new_p, new_m, new_aux, loss

    momenta = {n: jax.device_put(jnp.zeros_like(v), device)
               for n, v in params.items()}
    return jax.jit(step, donate_argnums=(0, 1, 2)), momenta


def _module_train_rate(mx, batch, dtype):
    """ResNet-50 training img/s through the framework's own path:
    symbol bind -> Module -> CachedTrainStep (one donated XLA program per
    step). Reference analogue: train_imagenet.py --benchmark 1
    (example/image-classification/README.md:255-260)."""
    import jax
    from mxnet_tpu import symbol as S
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import DataBatch, DataDesc
    from mxnet_tpu.module import Module

    net = vision.get_model("resnet50_v1", classes=1000)
    net.cast("bfloat16")
    out = net(S.Variable("data"))
    out = S.Cast(out, dtype="float32")
    out = S.SoftmaxOutput(out, S.Variable("softmax_label"), name="softmax")

    mod = Module(out, context=mx.tpu())
    mod.bind(
        data_shapes=[DataDesc("data", (batch, 3, 224, 224), dtype=dtype)],
        label_shapes=[DataDesc("softmax_label", (batch,),
                               dtype=np.float32)])
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),
                                         ("momentum", 0.9), ("wd", 1e-4)))
    rng = np.random.RandomState(0)
    db = DataBatch(
        [mx.nd.array(rng.rand(batch, 3, 224, 224).astype(np.float32),
                     dtype=dtype)],
        [mx.nd.array(rng.randint(0, 1000, (batch,)).astype(np.float32))])

    ex = mod._exec_group.execs[0]
    wname = next(n for n in ex.arg_names if n.endswith("weight"))

    def run():
        mod._fit_step(db)
        jax.block_until_ready(ex.arg_dict[wname]._data)

    rate, iters = _timed_rate(run, batch)
    if mod._cached_step is None:
        raise RuntimeError("module bench fell off the fused-step fast path")
    return rate, iters


def main():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit("bench: needs a TPU, jax found platform %r (%s); nothing "
                 "was measured" % (dev.platform, dev.device_kind))

    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.telemetry import costs
    from __graft_entry__ import _build_flagship

    peak = costs.device_peaks(dev.device_kind)[0]
    dtype = jnp.bfloat16
    forward, params, aux, _ = _build_flagship(batch=BATCH, dtype=dtype,
                                              device=dev)
    rng = np.random.RandomState(0)
    x = jax.device_put(jnp.asarray(rng.randn(BATCH, 3, 224, 224), dtype),
                       dev)
    y = jax.device_put(jnp.asarray(rng.randint(0, 1000, (BATCH,)),
                                   jnp.int32), dev)

    # ---- inference ----
    fwd = jax.jit(forward)

    def run_infer():
        jax.block_until_ready(fwd(params, aux, x))

    infer_rate, _ = _timed_rate(run_infer, BATCH)

    # ---- training (fwd + bwd + SGD update, donated) ----
    step, momenta = _build_train_step(forward, params, dev)
    state = {"p": params, "m": momenta, "a": aux}
    # Compile ONCE ahead of time; the executable serves both the FLOP
    # count and the timed loop (jit dispatch would compile separately).
    compiled = step.lower(state["p"], state["m"], state["a"], x, y).compile()
    step_flops, _ = costs.analyze_compiled(compiled)

    def run_train():
        state["p"], state["m"], state["a"], loss = compiled(
            state["p"], state["m"], state["a"], x, y)
        jax.block_until_ready(loss)

    train_rate, train_iters = _timed_rate(run_train, BATCH)

    # ---- training through the framework's own Module path ----
    module_rate, _ = _module_train_rate(mx, BATCH, dtype)

    achieved = step_flops * train_rate / BATCH        # FLOP/s
    print(json.dumps({
        "metric": "resnet50_train_bs32",
        "value": round(train_rate, 2),
        "unit": "img/s",
        "vs_baseline": round(train_rate / TRAIN_BASELINE_IMG_S, 2),
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "batch": BATCH,
        "infer_img_s": round(infer_rate, 2),
        "infer_vs_baseline": round(infer_rate / INFER_BASELINE_IMG_S, 2),
        "mfu": round(achieved / peak, 4),
        "step_gflops": round(step_flops / 1e9, 1),
        "tflops_achieved": round(achieved / 1e12, 1),
        "measure_iters": train_iters,
        "module_train_img_s": round(module_rate, 2),
        "module_vs_raw": round(module_rate / train_rate, 3),
    }))


if __name__ == "__main__":
    main()
